"""The benchmark's trace reduction and metric arithmetic, on hand-built
traces, and its one private read of the program (the chunk-completion
adapter) and the open loop's latency arithmetic against a tiny
``LiveServingEngine`` on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench
"""
import contextlib
import importlib.util
import math
import os

import numpy as np
import pytest

import gen
import program
import run
import tracing
from drivers import live

MS = 1_000_000  # ns


def _read(name, trace=None, **harness):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(run.BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run.Readings(trace, harness))


def _plane(ops, modules=()):
    return tracing.Plane("/device:TPU:0", list(ops), list(modules))


def test_busy_union_and_idle_share():
    # ops overlap (0-4 and 2-6 ms), touch (6-7) and leave gaps 7-9, 12-20
    ops = [("fusion.1", 0, 4 * MS), ("fusion.2", 2 * MS, 4 * MS),
           ("copy", 6 * MS, 1 * MS), ("_crm_kernel", 9 * MS, 3 * MS)]
    red = tracing.reduce([_plane(ops)], [], (0, 20 * MS))
    assert red.window_s == pytest.approx(0.020)
    assert red.busy_s == pytest.approx(0.010)
    assert red.idle_share == pytest.approx(0.5)
    assert red.op_s["fusion.1"] == pytest.approx(0.004)
    assert [round(s, 6) for _, s in red.idle_gaps] == [0.008, 0.002]


def test_busy_is_clipped_to_the_window_and_averaged_over_devices():
    a = _plane([("x", -5 * MS, 10 * MS)])            # 0-5 inside
    b = _plane([("y", 8 * MS, 10 * MS)])             # 8-10 inside
    red = tracing.reduce([a, b], [], (0, 10 * MS))
    assert red.busy_s == pytest.approx((0.005 + 0.002) / 2)


def test_idle_gaps_are_named_by_the_covering_host_span():
    ops = [("op", 0, 2 * MS), ("op", 5 * MS, 1 * MS)]
    spans = [("bench.window", 0, 10 * MS), ("bench.submit", 0, 4 * MS),
             ("bench.drain", 4 * MS, 6 * MS)]
    red = tracing.reduce([_plane(ops)], spans, (0, 10 * MS))
    # gaps 6-10 (drain), 2-5 (submit covers 2-4, drain 4-5)
    assert red.idle_gaps[0] == ("drain", pytest.approx(0.004))
    assert red.idle_gaps[1] == ("submit", pytest.approx(0.003))


def test_a_recorded_trace_keeps_the_harness_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    tracing.start(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        jnp.arange(8.0).sum().block_until_ready()
    jax.profiler.stop_trace()
    _, spans = tracing.load(str(tmp_path))
    assert [s[0] for s in spans] == ["bench.window"] and spans[0][2] > 0


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce([], [], (0, 1))


def test_scan_and_kernel_readers_match_programs_and_kernels():
    kernel = ('%crm_update.1 = f32[128,128] custom-call(f32[8,128] %pad), '
              'custom_call_target="tpu_custom_call"')
    ops = [("fusion", 0, 6 * MS), (kernel, 6 * MS, 1 * MS),
           (kernel.replace("crm_update.1", "merge_density.2"), 7 * MS, MS),
           ('%custom-call.8 = f32[4] custom-call(f64[4] %t), '
            'custom_call_target="X64SplitHigh"', 8 * MS, MS // 2)]
    mods = [("jit_step(7)", 0, 8 * MS), ("jit_convert", 8 * MS, 1 * MS)]
    red = tracing.reduce([_plane(ops, mods)], [], (0, 10 * MS))
    # 8 ms of scan program over 2M requests -> 4 ms per 1M
    assert _read("scan_ms_per_mreq", red, requests=2_000_000) == \
        pytest.approx(4.0)
    assert _read("cgm_kernel_share", red) == pytest.approx(2 / 8.5 * 100)
    assert _read("device_idle_share", red) == pytest.approx(15.0)


def test_readers_return_nothing_when_nothing_to_read():
    red = tracing.reduce([_plane([("fusion", 0, MS)])], [], (0, 2 * MS))
    assert _read("cgm_kernel_share", red) is None
    assert _read("scan_ms_per_mreq", red, requests=10) is None
    assert _read("chunk_fill_ms", red, requests=10) is None
    assert _read("gen_late_p95_ms", red, gen_late_ms=np.zeros(0)) is None


def test_harness_clock_readers():
    late = np.arange(100, dtype=float)             # 0..99 ms
    assert _read("gen_late_p95_ms", gen_late_ms=late) == \
        pytest.approx(np.percentile(late, 95))
    assert _read("chunk_fill_ms", chunk_fill_ms=np.array([3., 1., 2.])) == 2.0
    assert _read("chunk_inflight_ms",
                 chunk_inflight_ms=np.array([5., 9., 7., 1.])) == 6.0


def test_split_metric_falls_back_to_its_base_reader():
    red = tracing.reduce([_plane([("op", 0, MS)])], [], (0, 4 * MS))
    assert run._load_metric("device_idle_share.sweep")(
        run.Readings(red, {})) == pytest.approx(75.0)


def test_union_of_nested_and_disjoint_intervals():
    assert tracing.union_ns([(5, 6), (0, 4), (1, 2), (4, 5)]) == [[0, 6]]
    assert tracing.union_ns([(0, 1), (2, 3)]) == [[0, 1], [2, 3]]


def test_breakdown_names_ops_shortly_and_can_skip_containers():
    op_s = {"%while.1 = (f32[2]) while(...)": 5.0,
            "%fusion.7 = f32[2] fusion(...)": 3.0,
            "%conditional.2 = f32[2] conditional(...)": 2.0,
            "%copy.3 = f32[2] copy(...)": 1.0}
    assert tracing.top(op_s, 2) == [["while.1", 5.0], ["fusion.7", 3.0]]
    assert tracing.top(op_s, leaves=True) == [["fusion.7", 3.0],
                                              ["copy.3", 1.0]]


# -- the chunk-completion adapter and the open loop, on the CPU -------------

def _cell(mix, seconds, **traffic):
    """A Table II cell with traffic mix ``mix`` (``bench/traffic``), which
    need not be a workload of ``BENCHMARK.json`` yet."""
    spec = run._json(os.path.join(run.ROOT, "BENCHMARK.json"))
    spec["workloads"] = [{"name": "t2-netflix.test", "config":
                          "akpc-netflix-t2", "traffic": mix, "chips": 1}]
    cell = run.Cell(spec, "t2-netflix.test", 2**31 + 5, seconds)
    cell.traffic = {**cell.traffic, **traffic}
    return cell


def test_chunk_watch_sees_every_chunk_of_a_live_engine():
    cfg = _cell("live-closed", 1.0).cfg
    c = cfg["catalog"]
    log = gen.trace(cfg["trace"], c["n_items"], c["n_servers"], 2000,
                    2000 * cfg["trace"]["time_per_request"],
                    gen.rng_for(7, 0))
    eng = program.LiveServingEngine(program.policy(cfg), log.n, log.m,
                                    chunk_size=256)
    watch = program.ChunkWatch(eng, 0)
    for lo in range(0, 1900, 128):
        hi = min(lo + 128, 1900)
        eng.submit(log.items[lo:hi], log.servers[lo:hi], log.times[lo:hi])
        watch.submitted(hi)
    watch.drain(1900)
    ch = watch.chunks
    assert len(ch) == math.ceil(1900 / 256)
    assert [c[0] for c in ch] == [0] + [c[1] for c in ch[:-1]]
    assert ch[-1][1] == 1900 and watch.flushed
    assert all(c[3] is not None and c[3] >= c[2] for c in ch)
    # the device runs chunks in order, so they are stamped in order
    assert [c[3] for c in ch] == sorted(c[3] for c in ch)


def test_open_loop_prices_every_slice_due_in_the_window():
    cell = _cell("open-flash", 0.5, warm_requests=2000,
                 peak_req_per_s=12000)
    st = live.setup(cell)
    n0 = st["n_warm"]
    win = live.window(st, cell.seconds, lambda name: contextlib.nullcontext())
    h = win["harness"]
    end = st["log"].n_requests
    n_slices = math.ceil((end - n0) / st["slice"])
    # every slice has a latency, the drained remainder's too
    assert win["attempted"] == end - n0 > 0
    assert len(h["latency_ms"]) == len(h["gen_late_ms"]) == n_slices
    assert np.isfinite(h["latency_ms"]).all() and (h["latency_ms"] > 0).all()
    assert (h["gen_late_ms"] >= 0).all()      # never submitted early
    ch = h["chunks"]
    assert ch[-1, 1] == end and np.isfinite(ch[:, 3]).all()
    assert (ch[1:, 0] == ch[:-1, 1]).all()
    lat = np.sort(h["latency_ms"])
    assert win["metrics"]["p95_latency_ms"] == pytest.approx(
        np.percentile(lat, 95))
    assert lat[-1] <= win["elapsed_s"] * 1e3


def test_closed_loop_rate_is_every_request_over_the_whole_window():
    cell = _cell("live-closed", 0.3, warm_requests=2000, requests=6000)
    st = live.setup(cell)
    win = live.window(st, cell.seconds, lambda name: contextlib.nullcontext())
    assert win["attempted"] == st["submitted"] - st["n_warm"] > 0
    assert win["metrics"]["req_per_s"] == pytest.approx(
        win["attempted"] / win["elapsed_s"])
    assert win["elapsed_s"] >= cell.seconds or st["submitted"] == \
        st["log"].n_requests
