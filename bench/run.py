#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration is ``bench/configs/<config>.json``, its traffic mix
``bench/traffic/<traffic>.json``; the mix names the driver
(``bench/drivers/<driver>.py``) that feeds the program's entry point.
Each per-layer metric is read by ``bench/metrics/<metric>.py``.  So a
new cell, mix, configuration or metric is new files and new entries in
``BENCHMARK.json``, and no edit here.

A run: set-up (requests drawn from ``--seed``, the program warmed on
every shape the cell uses, compiled programs served from the persistent
cache in ``.jax_cache/`` of the checkout), the measured window of
``--seconds``, then the peak device memory, then the comparison of what
the window produced with the plain reference (``bench/reference``).
``--trace 1`` records the window with the profiler and reports the
per-layer metrics and a breakdown instead of the end-to-end ones.

Standard error ends with the compared numbers, each beside its limit;
the last line of standard output is the result as one JSON object.
Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
# the compile cache lives at one fixed path inside the checkout unless
# the environment names one; JAX reads this when it is imported
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(RuntimeError):
    pass


def _json(path: str):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, spec: dict, name: str, seed: int, seconds: float):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; have "
                             f"{sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        conf = {c["name"]: c for c in spec["configs"]}[self.entry["config"]]
        self.cfg = _json(os.path.join(ROOT, conf["file"]))
        self.traffic = _json(os.path.join(
            BENCH, "traffic", self.entry["traffic"] + ".json"))
        self.chips = int(self.entry["chips"])
        self.seed = seed
        self.seconds = seconds
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in spec["per_layer"]
                          if name in m["workloads"]
                          or ("workloads" not in m and m["moves"] in reported)]


def find_devices(chips: int):
    """The accelerator's devices; raises NoChip without enough of them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    check_peaks(devs[0].device_kind)
    return devs


def check_peaks(kind: str) -> None:
    """An accelerator missing from the peaks table is an error."""
    if kind not in _json(os.path.join(BENCH, "peaks.json"))["devices"]:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")


class Compiles:
    """Counts the programs JAX builds (compiled, or loaded from the
    persistent cache: both fire the backend-compile event), their
    seconds, and the persistent-cache hits."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.n, self.secs, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1
            self.secs += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            self.hits += 1


def _load_metric(name: str):
    """``metrics/<name>.py``; a split quantity such as ``<base>.<cells>``
    without a file of its own is read by ``metrics/<base>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    if not os.path.exists(path):
        path = os.path.join(BENCH, "metrics", name.split(".")[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Readings:
    """What a per-layer metric reader gets: the reduced trace and the
    driver's harness-clock statistics of the window."""

    def __init__(self, trace, harness: dict):
        self.trace = trace
        self.harness = harness


def run(argv=None, find=find_devices) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell(_json(os.path.join(ROOT, "BENCHMARK.json")), args.workload,
                args.seed, args.seconds)
    try:
        devs = find(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3

    import jax

    compiles = Compiles()
    import program
    driver = importlib.import_module("drivers." + cell.traffic["driver"])

    t_setup = time.perf_counter()
    state = driver.setup(cell)
    print(f"setup: {time.perf_counter() - T_START:.3f} s, of which "
          f"{t_setup - T_START:.3f} s to reach the device and "
          f"{compiles.secs:.3f} s building {compiles.n} programs "
          f"({compiles.hits} from the persistent cache)", file=sys.stderr)
    tracing = bool(args.trace)
    if tracing:
        import tracing as tr

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tr.start(TRACE_DIR)

    def span(name: str):
        if not tracing:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation("bench." + name)

    setup_s = time.perf_counter() - T_START
    c0, s0 = compiles.n, program.scan_traces()
    with span("window"):
        win = driver.window(state, args.seconds, span)
    window_compiles = compiles.n - c0
    window_traces = program.scan_traces() - s0
    if tracing:
        jax.profiler.stop_trace()
    used = devs[:cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)

    pairs = driver.answers(state)
    del state
    import compare
    from reference import akpc as reference

    t = time.perf_counter()
    ref_requests = 0
    checked = []
    for log, costs, got in pairs:
        checked.append((reference.run(log, costs, cell.cfg["policy"]), got))
        ref_requests += log.n_requests
    ref_s = time.perf_counter() - t
    correct, checks = compare.judge(checked, cell.cfg["guarantee"])

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": correct, "attempted": int(win["attempted"]),
           "failed": int(win.get("failed", 0))}
    print(f"window: {win['attempted']} requests in {win['elapsed_s']:.3f} s,"
          f" {window_compiles} compiles and {window_traces} scan traces "
          f"inside it", file=sys.stderr)
    print(f"reference: {len(checked)} answers, {ref_requests} requests in "
          f"{ref_s:.3f} s on the host ({ref_requests / max(ref_s, 1e-9):.0f}"
          f" req/s, context only)", file=sys.stderr)
    if tracing:
        devices, spans = tr.load(TRACE_DIR)
        w = [s for s in spans if s[0] == "bench.window"][0]
        for pl in devices:
            ends = [s + d for _, s, d in pl.ops]
            print(f"trace: {pl.name} {len(pl.ops)} ops "
                  f"[{min(s for _, s, _ in pl.ops) if ends else 0}, "
                  f"{max(ends) if ends else 0}] ns, {len(pl.modules)} "
                  f"program runs; window span [{w[1]}, {w[1] + w[2]}] ns",
                  file=sys.stderr)
        red = tr.reduce(devices, spans, (w[1], w[1] + w[2]))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        readings = Readings(red, win["harness"])
        metrics = {}
        for m in cell.per_layer:
            v = _load_metric(m["name"])(readings)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {"device_ops": tr.top(red.op_s, leaves=True),
                            "idle_gaps": [list(g) for g in red.idle_gaps]}
        print("programs: " + json.dumps(tr.top(red.module_s, 20)),
              file=sys.stderr)
        calls = sorted((n for n in red.op_s if "custom-call" in n),
                       key=lambda n: -red.op_s[n])
        for n in calls[:6]:
            print(f"custom call {red.op_s[n]:.6f} s: {n[:600]}",
                  file=sys.stderr)
        for name, stats in devices[0].kernel_stats:
            print(f"custom call stats: {name} {stats}", file=sys.stderr)
    else:
        # a split metric (``req_per_s.sweep``) is its base quantity
        e2e = dict(win["metrics"], setup_s=setup_s)
        out["metrics"] = {m["name"]: {"value": e2e[m["name"].split(".")[0]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    out["checks"] = checks
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
