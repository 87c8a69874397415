#!/usr/bin/env python
"""Chip smoke run: the replay, sweep and live-serving device paths on a TPU.

    python chip_smoke.py             # one chip: phases a-d
    python chip_smoke.py --chips 4   # four chips: the sharded paths only

Each phase drives a normal entry point once at a deployment size, checks
what comes out against the plain references (the numpy ``run_policy``
engine and the ``cliques_ref`` oracle) and prints one line: the route it
took, compile seconds, wall seconds, the device's peak bytes in use and
the largest relative cost deviation.  Integer counters and partitions
must match exactly and costs within 1e-9 relative; any miss raises and
the script exits non-zero.  The last line of stdout is one JSON object
naming the device.  Where JAX finds no TPU, the script exits non-zero
and prints no result.

Phases on one chip:

a. ``LiveServingEngine`` (AKPC, ``cgm="auto"``) streaming the Table II
   trace (60 items, 600 servers, 1M requests) in 128-request arrival
   slices, ``chunk_size=65536, ring=6`` as in ``serve_bench``.
b. ``run_policy(..., backend="jax")`` under the ``heterogeneous`` cost
   model: per-server dt, so the replay scan takes its segmented scans.
c. The fig7 theta x gamma x omega grid at n = 2000 through
   ``SweepEngine`` with the Mosaic CGM kernels, partitions checked
   window by window against ``cliques_ref``; plus the kernels alone at
   the routing bound ``MAX_DEVICE_CGM_HOT``.
d. A 100,000-item x 600-server catalog through ``SweepEngine`` with a
   ``bucketed`` state layout.

With ``--chips 4``: phase d's trace on a ``row_sharded`` layout over a
four-device state-row mesh against the same trace dense on one chip, and
the ``sweep_bench`` alpha x rho grid on a four-device scenario mesh
against the same grid on one chip.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import t_cg_for  # noqa: E402
from repro.core import (  # noqa: E402
    CacheEnvironment, CostParams, SweepEngine, SweepPoint, cgm_jax,
    get_cost_model, get_policy, run_policy,
)
from repro.core import cliques as cliques_mod  # noqa: E402
from repro.core.engine_jax import fresh_state_arrays  # noqa: E402
from repro.core.state_layout import StateLayout  # noqa: E402
from repro.serving import LiveServingEngine  # noqa: E402
from repro.traces import SynthConfig, paper_trace, synth_trace  # noqa: E402

REL_BAR = 1e-9
INT_FIELDS = ("n_requests", "n_item_requests", "n_misses", "n_hits",
              "items_transferred")
FLOAT_FIELDS = ("transfer", "caching", "keepalive_rent", "total")
#: phase d's catalog request count; 1M is what the synthetic generator
#: draws by default
CATALOG_REQUESTS = 1_000_000

_COMPILE_S = [0.0]


def _on_event(event: str, secs: float, **_kw) -> None:
    if event.startswith("/jax/core/compile/"):
        _COMPILE_S[0] += secs


def _peak_bytes() -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def compare_costs(tag: str, ref, got) -> float:
    """Ints exact, floats within REL_BAR; returns the largest relative
    deviation over the float fields."""
    a, b = ref.as_dict(), got.as_dict()
    for f in INT_FIELDS:
        if a[f] != b[f]:
            raise AssertionError(f"{tag}: {f} {a[f]} != reference {b[f]}")
    worst = 0.0
    for f in FLOAT_FIELDS:
        dev = abs(a[f] - b[f]) / max(abs(a[f]), 1e-300)
        worst = max(worst, dev)
        if dev > REL_BAR:
            raise AssertionError(
                f"{tag}: {f} {b[f]!r} vs reference {a[f]!r} "
                f"(relative {dev:.3e} > {REL_BAR})")
    return worst


def phase(name: str):
    """Run one phase, print its findings line, re-raise any failure."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*a, **k):
            c0, t0 = _COMPILE_S[0], time.perf_counter()
            info = fn(*a, **k)
            info.update(compile_s=round(_COMPILE_S[0] - c0, 3),
                        wall_s=round(time.perf_counter() - t0, 3),
                        peak_bytes=_peak_bytes())
            print(f"phase {name}: " + json.dumps(info), flush=True)
            return info
        return run
    return wrap


@phase("a live serving")
def phase_live(n_requests: int = 1_000_000, slice_n: int = 128) -> dict:
    tr = paper_trace("netflix", n_requests=n_requests, seed=0)
    params = CostParams()
    t_cg = t_cg_for(tr, params)

    def pol():
        return get_policy("akpc", params=params, t_cg=t_cg, top_frac=1.0)

    ref = run_policy(pol(), tr)
    calls0 = cliques_mod.CGM_CALLS
    live = LiveServingEngine(pol(), tr.n, tr.m, chunk_size=65536, ring=6)
    if not live._cgm:
        raise AssertionError("live engine did not fuse the device CGM")
    t0 = time.perf_counter()
    for lo in range(0, tr.n_requests, slice_n):
        hi = lo + slice_n
        live.submit(tr.items[lo:hi], tr.servers[lo:hi], tr.times[lo:hi])
    live.drain()
    stream_s = time.perf_counter() - t0
    if cliques_mod.CGM_CALLS != calls0:
        raise AssertionError("live engine ran the host CGM")
    got = live.result()
    if not np.array_equal(got.clique_sizes, ref.clique_sizes) \
            or got.n_windows != ref.n_windows:
        raise AssertionError("live partition differs from the reference")
    return {"route": "device CGM fused in the live scan",
            "kernels": "mosaic" if cgm_jax.kernels_on_backend() else "jnp",
            "requests": tr.n_requests, "windows": got.n_windows,
            "compiles": live.compiles, "stream_s": round(stream_s, 3),
            "max_rel_dev": compare_costs("live", ref.costs, got.costs)}


@phase("b per-server-dt replay")
def phase_hetero(n_requests: int = 1_000_000) -> dict:
    tr = paper_trace("netflix", n_requests=n_requests, seed=0)
    env = CacheEnvironment.skewed(tr.n, tr.m, price_sigma=1.0,
                                  size_sigma=0.75)
    t_cg = t_cg_for(tr, env=env, cost_model="heterogeneous")

    def pol():
        return get_policy("akpc", env=env, cost_model="heterogeneous",
                          t_cg=t_cg, top_frac=1.0)

    ref = run_policy(pol(), tr)
    got = run_policy(pol(), tr, backend="jax")
    if not np.array_equal(got.clique_sizes, ref.clique_sizes):
        raise AssertionError("per-server-dt partition differs")
    dt = np.asarray(get_cost_model("heterogeneous", env).dt())
    return {"route": "replay scan, host CGM, per-server dt "
                     "(jnp segmented scans)",
            "distinct_dt": int(np.unique(dt).size),
            "requests": tr.n_requests,
            "max_rel_dev": compare_costs("hetero", ref.costs, got.costs)}


@phase("c device-CGM sweep")
def phase_cgm_sweep() -> dict:
    from benchmarks.fig7_hyperparams import (
        PERF_N_ITEMS, PERF_N_REQUESTS, PERF_N_WINDOWS, SMOKE_GAMMAS,
        SMOKE_OMEGAS, SMOKE_THETAS, SMOKE_TOP_FRAC, device_partitions,
        partition_mismatches,
    )

    if not cgm_jax.kernels_on_backend():
        raise AssertionError("the Mosaic CGM kernels are not engaged")
    tr = synth_trace(SynthConfig(
        kind="spotify", n_items=PERF_N_ITEMS, n_servers=20,
        n_requests=PERF_N_REQUESTS, t_max=20.0, bundle_cover=1.0,
        bundle_zipf=0.7, seed=0))
    t_cg = float(tr.times[-1] - tr.times[0]) / PERF_N_WINDOWS
    combos = [(th, g, om) for th in SMOKE_THETAS for g in SMOKE_GAMMAS
              for om in SMOKE_OMEGAS]

    def kw(th, g, om):
        return dict(params=CostParams(theta=th, gamma=g, omega=om),
                    t_cg=t_cg, top_frac=SMOKE_TOP_FRAC)

    calls0 = cliques_mod.CGM_CALLS
    eng = SweepEngine(backend="jax")
    res = eng.run([SweepPoint("akpc", tr, kw(*c)) for c in combos])
    if cliques_mod.CGM_CALLS != calls0 or eng.stats["cgm_host_points"]:
        raise AssertionError("the sweep ran the host CGM")
    worst = 0.0
    for c, r in zip(combos, res):
        ref = run_policy(get_policy("akpc", **kw(*c)), tr)
        if not np.array_equal(r.clique_sizes, ref.clique_sizes):
            raise AssertionError(f"sweep partition differs at {c}")
        worst = max(worst, compare_costs(f"sweep {c}", ref.costs, r.costs))
    sched, final, ofs = device_partitions(tr, t_cg, combos, SMOKE_TOP_FRAC)
    bad = partition_mismatches(tr, t_cg, combos, SMOKE_TOP_FRAC,
                               sched, final, ofs)
    if bad:
        raise AssertionError("; ".join(bad))
    return {"route": "device CGM in the vmapped sweep scan",
            "kernels": "mosaic", "points": len(combos),
            "merge_space": eng.stats["cgm_merge_space"],
            "schedules": eng.last_n_schedules, "h": int(sched.h),
            "windows": int(sched.boundary_steps.size),
            "partitions": "equal to cliques_ref at every window",
            "max_rel_dev": worst, **kernels_at_bound()}


def kernels_at_bound(h: int = cgm_jax.MAX_DEVICE_CGM_HOT) -> dict:
    """The three CGM kernels at the routing bound ``h`` against numpy."""
    import jax.numpy as jnp

    from repro.core.cliques import _densities
    from repro.kernels.clique_density import clique_pair_edges
    from repro.kernels.crm_update import crm_update
    from repro.kernels.merge_step import merge_density, merge_edge_floor

    rng = np.random.default_rng(0)
    M = (rng.random((2 * h, h)) < 0.01).astype(np.float32)
    A = (rng.random((h, h)) < 0.2).astype(np.float32)
    A = np.maximum(A, A.T)
    with jax.enable_x64(True):
        X = np.asarray(clique_pair_edges(jnp.asarray(M), jnp.asarray(A)))
        Md = M.astype(np.float64)
        if not np.array_equal(X, Md @ A.astype(np.float64) @ Md.T):
            raise AssertionError("clique_pair_edges is not exact at the bound")
        H = (rng.random((4096, h)) < 0.002).astype(np.float32)
        C = np.asarray(crm_update(jnp.asarray(H)))
        want = H.astype(np.float64).T @ H.astype(np.float64)
        np.fill_diagonal(want, 0.0)
        if not np.array_equal(C, want):
            raise AssertionError("crm_update is not exact at the bound")
        # counts up to e_max = 10 so the density bar cuts through them
        Xs = np.minimum(X, 4.0)
        sizes = rng.integers(0, 4, 2 * h).astype(np.int32)
        omega, gamma = 5, 0.6
        D = np.asarray(merge_density(
            jnp.asarray(Xs), jnp.asarray(sizes), jnp.int32(omega),
            jnp.asarray(merge_edge_floor(omega, gamma))))
    within = np.diag(Xs) / 2.0
    e_u = (within[:, None] + within[None, :]) + Xs
    dens = _densities(Xs, sizes, omega)
    want_d = np.where(dens >= gamma, e_u, -1.0).astype(np.float32)
    if not np.array_equal(D, want_d):
        raise AssertionError(
            f"merge_density differs from numpy at "
            f"{int((D != want_d).sum())} entries")
    return {"kernels_at_h": h, "kernels_exact": True}


def catalog_trace(n_requests: int):
    return synth_trace(SynthConfig(
        kind="netflix", n_items=100_000, n_servers=600,
        n_requests=n_requests, t_max=6.0 * n_requests / 100_000.0,
        bundle_cover=1.0, bundle_zipf=0.7, server_affinity=2, seed=0))


def catalog_route(tr, params, t_cg, layout) -> str:
    pol = get_policy("akpc", params=params, t_cg=t_cg)
    pol.bind(tr.n, tr.m)
    model = get_cost_model("table1", CacheEnvironment.resolve(None, tr,
                                                              params))
    dev = (cgm_jax.wants_device_cgm(pol, tr, model)
           and layout.supports_device_cgm(tr.n, tr.m))
    return "device CGM" if dev else "host CGM, replay scan"


@phase("d catalog at deployment scale")
def phase_catalog(n_requests: int = CATALOG_REQUESTS) -> dict:
    tr = catalog_trace(n_requests)
    params = CostParams()
    t_cg = t_cg_for(tr, params)
    layout = StateLayout(kind="bucketed")
    pt = SweepPoint("akpc", tr, dict(params=params, t_cg=t_cg))
    got = SweepEngine(backend="jax", layout=layout).run([pt])[0]
    ref = run_policy(get_policy("akpc", params=params, t_cg=t_cg), tr)
    if not np.array_equal(got.clique_sizes, ref.clique_sizes):
        raise AssertionError("catalog partition differs")
    return {"route": catalog_route(tr, params, t_cg, layout),
            "layout": layout.tag, "n_items": tr.n, "n_servers": tr.m,
            "requests": tr.n_requests,
            "state_bytes": layout.state_bytes(tr.n, tr.m),
            "max_rel_dev": compare_costs("catalog", ref.costs, got.costs)}


@phase("4-chip row-sharded catalog")
def phase_row_sharded(n_requests: int = CATALOG_REQUESTS) -> dict:
    from repro.launch.mesh import make_sweep_mesh

    tr = catalog_trace(n_requests)
    params = CostParams()
    t_cg = t_cg_for(tr, params)
    pt = SweepPoint("akpc", tr, dict(params=params, t_cg=t_cg))
    mesh = make_sweep_mesh(state_rows=4)
    layout = StateLayout(kind="row_sharded", mesh=mesh)
    E0, a0 = layout.place_state(*fresh_state_arrays(tr.n, tr.m, layout))
    spans = len(E0.sharding.device_set)
    del E0, a0
    got = SweepEngine(backend="jax", mesh=mesh, layout=layout).run([pt])[0]
    sharded_peaks = _peak_bytes()
    one = SweepEngine(backend="jax").run([pt])[0]
    if spans != 4:
        raise AssertionError(f"row-sharded state spans {spans} devices")
    if not np.array_equal(got.clique_sizes, one.clique_sizes):
        raise AssertionError("row-sharded partition differs from one chip")
    return {"route": catalog_route(tr, params, t_cg, layout),
            "layout": layout.tag, "state_devices": spans,
            "peak_bytes_after_sharded_run": sharded_peaks,
            "state_bytes_per_device": layout.state_bytes_per_device(
                tr.n, tr.m),
            "requests": tr.n_requests,
            "max_rel_dev_vs_one_chip": compare_costs(
                "row_sharded", one.costs, got.costs)}


@phase("4-chip scenario-mesh sweep")
def phase_scenario_mesh(n_requests: int = 150_000, n_alphas: int = 64,
                        n_rhos: int = 4) -> dict:
    from benchmarks.sweep_bench import build_grid
    from repro.launch.mesh import make_sweep_mesh

    tr = paper_trace("netflix", n_requests=n_requests, seed=0)
    pts = build_grid(tr, n_alphas, n_rhos)
    mesh = make_sweep_mesh()
    got = SweepEngine(backend="jax", mesh=mesh).run(pts)
    one = SweepEngine(backend="jax").run(pts)
    worst = 0.0
    for pt, a, b in zip(pts, one, got):
        worst = max(worst, compare_costs(pt.tag, a.costs, b.costs))
    return {"route": "scenario axis over the mesh", "points": len(pts),
            "mesh": dict(mesh.shape), "requests": tr.n_requests,
            "max_rel_dev_vs_one_chip": worst}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded paths and their "
                         "one-chip comparisons")
    args = ap.parse_args(argv)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    print(f"device: {devs[0].device_kind} x{len(devs)}, jax "
          f"{jax.__version__}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    if args.chips == 4:
        phase_row_sharded()
        phase_scenario_mesh()
    else:
        phase_live()
        phase_hetero()
        phase_cgm_sweep()
        phase_catalog()
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
