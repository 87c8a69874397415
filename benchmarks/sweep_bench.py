"""Vmapped sweep vs serial per-point replay on a fig6-style grid.

The PR-5 acceptance benchmark: an (alpha x rho) sensitivity grid of AKPC
points — the exact shape of benchmarks/fig6_sensitivity.py — replayed two
ways on the same machine:

* **serial**: the pre-PR-5 loop — one ``run_policy`` (NumPy engine) per
  grid point, clique generation re-run every time;
* **sweep**:  one ``SweepEngine`` call — points sharing (trace, CGM
  hyperparameters) share a host schedule (every alpha row shares one
  clique-generation pass per rho), and each schedule group replays as a
  single vmapped ``jit``/``lax.scan`` on device.

The sweep is timed twice: **cold** (first call of the process — schedule
build + XLA compile, or a hit in the persistent compile cache that
``SweepEngine`` enables) and **warm** (second call — the steady state of
every realistic sweep workload, where the compiled cohort is cached
across ``SweepEngine.run`` calls).  Cost parity at 1e-9 between serial
and sweep is asserted for EVERY point before any timing is trusted.
Results land in ``experiments/results/BENCH_sweep.json`` so the perf
trajectory records both paths and the measured speedups.

Env knobs:
  REPRO_SWEEP_BENCH_REQUESTS   trace length per point   (default 150000)
  REPRO_SWEEP_BENCH_ALPHAS     alpha-axis size          (default 64)
  REPRO_SWEEP_BENCH_RHOS       rho-axis size            (default 4)

``--smoke`` (CI): 60k-request trace, 32-point grid, parity check + the
warm sweep must BEAT the serial loop (no 5x floor — CI runners are too
noisy to gate on a ratio; the full run asserts >= 5x cold).  Small grids
used to LOSE cold (0.88x at 24 points/40k requests: one ~1s XLA compile
outweighed the vmap win); the compiled-cohort caches fixed that — cold
runs hit the on-disk cache from the second process on, and warm runs
never re-trace.  Smoke also runs the ISSUE-8 mixed-shape gate: a grid
of four distinct (n, m) points under a ``bucketed`` StateLayout must
compile once per bucket COHORT, not once per point.

``--mesh`` (devices x points): re-times the warm sweep in THIS process
on 1, 2 and 4-device sub-meshes of ``jax.devices()`` (``make_sweep_mesh
(n_devices=d)``; rows above the host's device count are skipped) and
records one scaling row per device count in BENCH_sweep.json.  One
process holds the chip, so no worker process is started.  On CPU give
the process virtual devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``: that record is
the scaling SHAPE, not a speedup claim.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

from repro.core import CostParams, SweepEngine, SweepPoint
from repro.core.state_layout import StateLayout
from repro.traces import paper_trace

from .common import emit, save_json, t_cg_for

INT_FIELDS = ("n_requests", "n_item_requests", "n_misses", "n_hits",
              "items_transferred")
FLOAT_FIELDS = ("transfer", "caching", "keepalive_rent", "total")


def build_grid(trace, n_alphas: int, n_rhos: int) -> list[SweepPoint]:
    """fig6-style grid: alpha x rho sensitivity of the proposed method."""
    alphas = np.linspace(0.6, 1.0, n_alphas)
    rhos = np.linspace(1.0, 6.0, n_rhos)
    pts = []
    for rho in rhos:
        for alpha in alphas:
            params = CostParams(alpha=float(alpha), rho=float(rho))
            pts.append(SweepPoint(
                "akpc", trace,
                dict(params=params, t_cg=t_cg_for(trace, params),
                     top_frac=1.0),
                tag=f"alpha={alpha:.3f}/rho={rho:.2f}"))
    return pts


def assert_parity(pts, serial, swept) -> None:
    for pt, a, b in zip(pts, serial, swept):
        da, db = a.costs.as_dict(), b.costs.as_dict()
        for f in INT_FIELDS:
            assert da[f] == db[f], (pt.tag, f, da[f], db[f])
        for f in FLOAT_FIELDS:
            assert np.isclose(da[f], db[f], rtol=1e-9, atol=1e-9), \
                (pt.tag, f, da[f], db[f])


def state_bytes_telemetry(n: int, m: int) -> dict:
    """Device state-buffer bytes per layout at (n, m) — the catalog-scale
    memory record ISSUE 8 tracks across PRs alongside wall-clock."""
    return {
        "n_items": n, "n_servers": m,
        "dense": StateLayout().state_bytes(n, m),
        "bucketed": StateLayout(kind="bucketed").state_bytes(n, m),
        "row_sharded_x4_per_device": StateLayout(
            kind="row_sharded", shards=4).state_bytes_per_device(n, m),
    }


def mixed_shape_gate() -> dict:
    """Bucketed-compilation contract on a mixed-(n, m) grid: compile
    count (SCAN_TRACES delta) <= #bucket-cohorts, strictly < #points."""
    from repro.core import engine_jax as ej
    from repro.traces import SynthConfig, synth_trace

    lay = StateLayout(kind="bucketed", row_bucket=64, col_bucket=32)
    shapes = [(50, 20), (60, 25), (100, 40), (120, 48)]
    pts = []
    for seed, (n, m) in enumerate(shapes):
        tr = synth_trace(SynthConfig(
            kind="netflix", n_items=n, n_servers=m, n_requests=3000,
            t_max=3.0, bundle_cover=1.0, bundle_zipf=0.7, seed=seed))
        params = CostParams()
        pts.append(SweepPoint(
            "akpc", tr,
            dict(params=params, t_cg=t_cg_for(tr, params), top_frac=1.0),
            tag=f"n={n}/m={m}"))
    cohorts = len({lay.state_dims(n, m) for n, m in shapes})
    traces0 = ej.SCAN_TRACES
    jax_res = SweepEngine(backend="jax", layout=lay).run(pts)
    compiles = ej.SCAN_TRACES - traces0
    ref = SweepEngine(backend="numpy").run(pts)
    assert_parity(pts, ref, jax_res)
    assert cohorts < len(pts), "gate grid must be mixed-shape"
    assert compiles <= cohorts, (
        f"bucketed mixed-shape sweep compiled {compiles}x for "
        f"{cohorts} cohorts ({len(pts)} points)")
    print(f"# mixed-shape gate: {len(pts)} points -> {cohorts} cohorts, "
          f"{compiles} compiles, parity OK")
    return {"points": len(pts), "cohorts": cohorts, "compiles": compiles,
            "layout": {"tag": lay.tag, "row_bucket": lay.row_bucket,
                       "col_bucket": lay.col_bucket}}


def bench_mesh(trace, n_alphas: int, n_rhos: int) -> list[dict]:
    """Devices x points scaling rows on 1/2/4-device sub-meshes."""
    import jax

    from repro.core.state_layout import make_sweep_mesh

    pts = build_grid(trace, n_alphas, n_rhos)
    rows = []
    for d in (1, 2, 4):
        if d > len(jax.devices()):
            break
        eng = SweepEngine(backend="jax", mesh=make_sweep_mesh(n_devices=d))
        eng.run(pts)                       # compile / cache-hit pass
        t0 = time.perf_counter()
        eng.run(pts)
        row = {"devices": d, "points": len(pts),
               "warm_seconds": time.perf_counter() - t0}
        rows.append(row)
        print(f"# mesh: {d} device(s) -> {row['warm_seconds']:.2f}s warm")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small CI run: parity + sweep must beat serial")
    ap.add_argument("--mesh", action="store_true",
                    help="record devices x points mesh scaling rows")
    args, _ = ap.parse_known_args()

    if args.smoke:
        n = int(os.environ.get("REPRO_SWEEP_BENCH_REQUESTS", "60000"))
        n_alphas = int(os.environ.get("REPRO_SWEEP_BENCH_ALPHAS", "16"))
        n_rhos = int(os.environ.get("REPRO_SWEEP_BENCH_RHOS", "2"))
    else:
        n = int(os.environ.get("REPRO_SWEEP_BENCH_REQUESTS", "150000"))
        n_alphas = int(os.environ.get("REPRO_SWEEP_BENCH_ALPHAS", "64"))
        n_rhos = int(os.environ.get("REPRO_SWEEP_BENCH_RHOS", "4"))

    trace = paper_trace("netflix", n_requests=n, seed=0)
    pts = build_grid(trace, n_alphas, n_rhos)

    # -- serial baseline: the pre-PR-5 per-point loop, same machine --------
    serial_eng = SweepEngine(backend="numpy")
    t0 = time.perf_counter()
    serial = serial_eng.run(pts)
    t_serial = time.perf_counter() - t0

    # -- vmapped sweep: cold (schedule build + compile-or-cache-hit),
    # then warm (compiled cohort reused across SweepEngine.run calls) ------
    sweep_eng = SweepEngine(backend="jax")
    t0 = time.perf_counter()
    swept = sweep_eng.run(pts)
    t_sweep = time.perf_counter() - t0
    t0 = time.perf_counter()
    swept_warm = sweep_eng.run(pts)
    t_warm = time.perf_counter() - t0

    assert_parity(pts, serial, swept)
    assert_parity(pts, serial, swept_warm)
    print(f"# parity check on {len(pts)} points (cold + warm): OK")

    speedup = t_serial / t_sweep
    speedup_warm = t_serial / t_warm
    emit([
        (f"sweep/serial_{len(pts)}pts", int(t_serial / len(pts) * 1e6),
         f"{t_serial:.2f}s total"),
        (f"sweep/vmapped_{len(pts)}pts", int(t_sweep / len(pts) * 1e6),
         f"{t_sweep:.2f}s total;{sweep_eng.last_n_schedules} schedules"),
        (f"sweep/vmapped_warm_{len(pts)}pts", int(t_warm / len(pts) * 1e6),
         f"{t_warm:.2f}s total"),
        ("sweep/speedup", round(speedup, 2), "x cold"),
        ("sweep/speedup_warm", round(speedup_warm, 2), "x warm"),
    ])
    payload = {
        "n_requests": n,
        "grid": {"alphas": n_alphas, "rhos": n_rhos, "points": len(pts)},
        "policy": "akpc",
        "cost_model": "table1",
        "serial_seconds": t_serial,
        "sweep_seconds": t_sweep,
        "sweep_warm_seconds": t_warm,
        "speedup": speedup,
        "speedup_warm": speedup_warm,
        "n_schedules": sweep_eng.last_n_schedules,
        "smoke": bool(args.smoke),
        "points_per_second_serial": len(pts) / t_serial,
        "points_per_second_sweep": len(pts) / t_sweep,
        "points_per_second_sweep_warm": len(pts) / t_warm,
        "state_layout": sweep_eng.layout.tag,
        "state_bytes": state_bytes_telemetry(trace.n, trace.m),
    }
    if args.smoke:
        payload["mixed_shape"] = mixed_shape_gate()
    if args.mesh:
        payload["mesh_scaling"] = bench_mesh(trace, n_alphas, n_rhos)
    save_json("BENCH_sweep", payload)
    if args.smoke:
        assert t_warm < t_serial, (
            f"warm vmapped sweep ({t_warm:.2f}s) no faster than the "
            f"serial loop ({t_serial:.2f}s)")
    else:
        assert speedup >= 5.0, \
            f"vmapped sweep only {speedup:.1f}x faster than serial"


if __name__ == "__main__":
    main()
