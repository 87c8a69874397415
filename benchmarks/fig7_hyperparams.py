"""Fig. 7 — hyper-parameter sensitivity: (a) CRM threshold theta,
(b) clique-approximation threshold gamma, (c) max clique size omega.

All three axes over both traces run as ONE ``run_method_grid`` sweep
call (PR 5).  Since PR 6 the clique-generation module itself runs inside
the jit'd scan (DESIGN.md §11), so a theta x gamma x omega grid shares
ONE partition-free schedule and vmaps the CGM knobs as scenario lanes.

``--smoke`` (CI) is the device-CGM oracle gate: the on-device clique
generation must reproduce the frozen ``cliques_ref`` oracle
element-for-element at EVERY chained T_CG boundary over a small
theta x gamma x omega grid, and a fig7-style sweep must perform ZERO
host clique-generation calls (the ``cliques.CGM_CALLS`` counter stays
flat) while sharing one schedule.

It also runs the compact-CGM perf gate (same style as the fig9 gate:
the shipped implementation against its predecessor, timed on the same
machine): on a catalog far above the old 256-item cap, the compact
hot-space boundary's per-window marginal must beat the full
``(n, n)``-workspace layout it replaced, with the host CGM walk
recorded alongside in ``BENCH_cgm.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from .common import (
    N_SWEEP, RESULTS_DIR, emit, get_trace, get_trace_shards,
    relative_to_opt, run_method_grid, save_json, t_cg_for,
)
from repro.core import CostParams

THETAS = [0.05, 0.1, 0.15, 0.2, 0.3, 0.5]
GAMMAS = [0.6, 0.7, 0.8, 0.85, 0.9, 1.0]
OMEGAS = [2, 3, 5, 7, 10]
METHODS = ("akpc", "akpc_base", "opt")
KINDS = ("netflix", "spotify")

SMOKE_THETAS = (0.1, 0.3)
SMOKE_GAMMAS = (0.6, 0.9)
SMOKE_OMEGAS = (3, 5)
SMOKE_TOP_FRAC = 0.5


def main() -> list[tuple]:
    grid, keys = [], []
    for kind in KINDS:
        tr = get_trace_shards(kind, N_SWEEP)
        for axis, values, mk in (
            ("theta", THETAS, lambda v: CostParams(theta=v)),
            ("gamma", GAMMAS, lambda v: CostParams(gamma=v)),
            ("omega", OMEGAS, lambda v: CostParams(omega=v)),
        ):
            for v in values:
                grid.append({"trace": tr, "params": mk(v),
                             "methods": METHODS, "cost_model": "table1"})
                keys.append((axis, kind, v))
    results = run_method_grid(grid)

    rows, payload = [], {"theta": {}, "gamma": {}, "omega": {}}
    tags = {"theta": "fig7a", "gamma": "fig7b", "omega": "fig7c"}
    for (axis, kind, val), res in zip(keys, results):
        rel = relative_to_opt(res)
        payload[axis].setdefault(kind, {})[val] = rel
        rows.append((f"{tags[axis]}/{kind}/{axis}={val}", 0,
                     f"akpc={rel['akpc']};base={rel['akpc_base']}"))
    save_json("fig7_hyperparams", payload)
    emit(rows)
    return rows


def oracle_partitions(tr, t_cg, theta, gamma, omega, top_frac):
    """``cliques_ref`` slot maps at every T_CG boundary, walked exactly as
    the replay engines walk the windows."""
    from repro.core import cliques_ref
    from repro.core.crm import build_window_crm

    times, R = tr.times, tr.n_requests
    next_cg = float(times[0]) + t_cg
    win_start = pos = 0
    prev = prev_crm = None
    parts = []
    while pos < R:
        cut = int(np.searchsorted(times, next_cg, side="left"))
        if cut <= pos:
            t = float(times[pos])
            crm = build_window_crm(
                tr.items[win_start:pos], tr.n, theta, top_frac=top_frac)
            prev = cliques_ref.generate_cliques(
                prev, prev_crm, crm, tr.n, omega, gamma)
            parts.append(prev.clique_of.copy())
            prev_crm = crm
            win_start = pos
            while next_cg <= t:
                next_cg += t_cg
            continue
        pos = cut
    return parts


def device_partitions(tr, t_cg, combos, top_frac):
    """One vmapped device-CGM replay over the ``(theta, gamma, omega)``
    lanes; returns (schedule, final carry, per-step slot maps)."""
    from repro.core import CacheEnvironment, cgm_jax, get_policy
    from repro.core.engine_jax import JaxReplayEngine

    def policy(th, g, om):
        p = get_policy("akpc", params=CostParams(theta=th, gamma=g, omega=om),
                       t_cg=t_cg, top_frac=top_frac)
        p.bind(tr.n, tr.m)
        return p

    pol0 = policy(*combos[0])
    env = CacheEnvironment.resolve(None, tr, pol0.params)
    jeng = JaxReplayEngine(tr.n, tr.m, pol0.params, env=env)
    sched = cgm_jax.build_cgm_schedule(
        tr, t_cg, uses_sizes=False, hot_dims=cgm_jax.policy_hot_dims(pol0))
    cspecs = [cgm_jax.cgm_spec(p.config, p.config.params, tr.n)
              for p in (policy(*c) for c in combos)]
    cspec = {k: np.stack([np.asarray(cs[k]) for cs in cspecs])
             for k in cspecs[0]}
    S = len(combos)
    carry1 = cgm_jax.init_cgm_carry(
        jeng.engine.state, None, None, n=tr.n, m=tr.m,
        uses_sizes=False, item_sizes=None, schedule=sched)
    carry0 = {k: np.stack([v] * S) for k, v in carry1.items()}
    spec = {k: np.stack([v] * S) for k, v in jeng._spec.items()}
    final, ofs = cgm_jax.run_cgm_schedule(
        sched, spec, jeng._statics, cspec, carry0, None)
    return sched, final, ofs


def partition_mismatches(tr, t_cg, combos, top_frac, sched, final, ofs):
    """Lanes whose device partitions differ from the oracle at any
    chained boundary (empty when every window matches)."""
    nbd = int(sched.boundary_steps.size)
    failures = []
    for lane, (th, g, om) in enumerate(combos):
        want = oracle_partitions(tr, t_cg, th, g, om, top_frac)
        if len(want) != nbd:
            failures.append(f"theta={th} gamma={g} omega={om}: "
                            f"{len(want)} oracle windows vs {nbd} device")
            continue
        bad = [w for w, (b, ref_of) in
               enumerate(zip(sched.boundary_steps, want))
               if not np.array_equal(ofs[lane, int(b)], ref_of)]
        if bad or not np.array_equal(final["of"][lane], want[-1]):
            failures.append(f"theta={th} gamma={g} omega={om}: partition "
                            f"mismatch at windows {bad or ['final']}")
    return failures


def smoke() -> None:
    """CI gate: device-CGM partitions == ``cliques_ref`` oracle, chained."""
    from repro.core import SweepEngine, SweepPoint
    from repro.core import cliques as cliques_mod

    tr = get_trace("netflix", 4000)
    t_cg = t_cg_for(tr, CostParams())
    combos = [(th, g, om) for th in SMOKE_THETAS for g in SMOKE_GAMMAS
              for om in SMOKE_OMEGAS]

    def kw(th, g, om):
        return dict(params=CostParams(theta=th, gamma=g, omega=om),
                    t_cg=t_cg, top_frac=SMOKE_TOP_FRAC)

    # -- one vmapped device call over the whole grid -----------------------
    before = cliques_mod.CGM_CALLS
    sched, final, ofs = device_partitions(tr, t_cg, combos, SMOKE_TOP_FRAC)
    nbd = int(sched.boundary_steps.size)
    assert nbd >= 3, f"need chained windows, got {nbd}"
    S = len(combos)
    failures = []
    if cliques_mod.CGM_CALLS != before:
        failures.append("device replay performed host CGM calls")
    failures += partition_mismatches(
        tr, t_cg, combos, SMOKE_TOP_FRAC, sched, final, ofs)

    # -- a fig7-style sweep: one schedule, zero host CGM calls -------------
    eng = SweepEngine()
    before = cliques_mod.CGM_CALLS
    eng.run([SweepPoint("akpc", tr, kw(*c)) for c in combos])
    if cliques_mod.CGM_CALLS != before:
        failures.append("fig7 sweep performed host CGM calls")
    if eng.last_n_schedules != 1:
        failures.append(f"fig7 sweep built {eng.last_n_schedules} "
                        "schedules, expected 1 shared")

    # -- perf gate (fig9-gate style: the shipped implementation against
    # its predecessor, timed on the same machine).  The compact hot-space
    # boundary must beat the full (n, n)-catalog workspace it replaced
    # per window on a catalog far above the old 256-item cap; both
    # variants compute the SAME partitions, so the timing comparison is
    # also a layout-parity check.  The host CGM walk rides along as the
    # recorded yardstick (BENCH_cgm.json "compact_vs_dense_vs_host").
    perf = _perf_breakdown()
    if perf["compact_us_per_window"] >= perf["dense_us_per_window"]:
        failures.append(
            f"compact device CGM {perf['compact_us_per_window']}us/window "
            f">= dense (n, n) workspace {perf['dense_us_per_window']}"
            "us/window on this machine (the compact hot space must win)")
    if not perf["layouts_agree"]:
        failures.append(
            "compact and dense (n, n) workspaces produced DIFFERENT "
            "partitions — the layouts must be semantics-preserving")

    emit([("fig7/smoke_oracle_gate", 0,
           f"grid={S}pts;windows={nbd};"
           f"status={'FAIL' if failures else 'OK'}"),
          ("fig7/smoke_cgm_perf_gate", perf["compact_us_per_window"],
           f"n={perf['n']};windows={perf['windows']};"
           f"compact_us_per_window={perf['compact_us_per_window']};"
           f"dense_us_per_window={perf['dense_us_per_window']};"
           f"host_us_per_window={perf['host_us_per_window']};"
           f"speedup_vs_dense={perf['speedup_vs_dense']};"
           f"status={'FAIL' if failures else 'OK'}")])
    if failures:
        print("DEVICE-CGM ORACLE GATE FAILED:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        sys.exit(1)
    print(f"# device-CGM oracle gate: {S} grid points x {nbd} chained "
          "windows, all partitions identical, zero host CGM calls")
    print(f"# compact-CGM perf gate: n={perf['n']} "
          f"compact={perf['compact_us_per_window']}us/window vs "
          f"dense={perf['dense_us_per_window']}us "
          f"({perf['speedup_vs_dense']}x) vs "
          f"host={perf['host_us_per_window']}us")


#: perf-gate catalog — far above the old MAX_DEVICE_CGM_N = 256 cap, so
#: the compact (h, h) workspace is genuinely smaller than the (n, n)
#: predecessor layout it is timed against
PERF_N_ITEMS = 2000
PERF_N_REQUESTS = 3000
PERF_N_WINDOWS = 12


def _perf_breakdown() -> dict:
    """Per-window wall time of the device-CGM boundary in the compact
    hot space vs the dense ``(n, n)`` predecessor workspace vs the
    vectorized host CGM — all on the same trace and machine.

    Device costs are replay MARGINALS: the same schedule replayed with
    boundaries enabled minus a clique-generation-zeroed replay, so the
    shared scan cost cancels and only the Alg. 2-4 boundary work is
    charged.  The dense variant is the SAME compact machinery with the
    workspace forced to the full catalog (``h = n``) — what every
    boundary paid before the compact carry — and must reproduce the
    compact partitions element-for-element.
    """
    import dataclasses
    import time

    from repro.core import (
        CacheEnvironment, CostParams, cgm_jax, get_policy,
    )
    from repro.core import cliques as cliques_mod
    from repro.core.crm import build_window_crm
    from repro.core.engine_jax import JaxReplayEngine
    from repro.traces import SynthConfig, synth_trace

    tr = synth_trace(SynthConfig(
        kind="spotify", n_items=PERF_N_ITEMS, n_servers=20,
        n_requests=PERF_N_REQUESTS, t_max=20.0, bundle_cover=1.0,
        bundle_zipf=0.7, seed=0))
    span = float(tr.times[-1] - tr.times[0])
    t_cg = span / PERF_N_WINDOWS
    params = CostParams()
    pol = get_policy("akpc", params=params, t_cg=t_cg,
                     top_frac=SMOKE_TOP_FRAC)
    pol.bind(tr.n, tr.m)
    env = CacheEnvironment.resolve(None, tr, pol.params)
    jeng = JaxReplayEngine(tr.n, tr.m, pol.params, env=env)
    sched = cgm_jax.build_cgm_schedule(
        tr, t_cg, uses_sizes=False, hot_dims=cgm_jax.policy_hot_dims(pol))
    nbd = int(sched.boundary_steps.size)
    cspec = cgm_jax.cgm_spec(pol.config, pol.config.params, tr.n)

    def marginal(schedule):
        carry0 = cgm_jax.init_cgm_carry(
            jeng.engine.state, None, None, n=tr.n, m=tr.m,
            uses_sizes=False, item_sizes=None, schedule=schedule)
        zeroed = dataclasses.replace(
            schedule, xs=dict(schedule.xs,
                              cg=np.zeros_like(schedule.xs["cg"])))

        def run(s):
            final, ofs = cgm_jax.run_cgm_schedule(
                s, jeng._spec, jeng._statics, cspec, carry0, None)
            return np.asarray(final["of"]), np.asarray(ofs)

        of, ofs = run(schedule)          # compile + warm
        run(zeroed)
        t_force = t_zero = float("inf")
        for _ in range(3):               # interleaved, min-based
            t0 = time.perf_counter()
            run(schedule)
            t_force = min(t_force, time.perf_counter() - t0)
            t0 = time.perf_counter()
            run(zeroed)
            t_zero = min(t_zero, time.perf_counter() - t0)
        return (t_force - t_zero) / nbd, of, ofs

    compact_pw, of_c, ofs_c = marginal(sched)
    dense_pw, of_d, ofs_d = marginal(dataclasses.replace(sched, h=tr.n))

    def host_walk():
        prev = prev_crm = None
        win_start = pos = 0
        next_cg = float(tr.times[0]) + t_cg
        while pos < tr.n_requests:
            cut = int(np.searchsorted(tr.times, next_cg, side="left"))
            if cut <= pos:
                crm = build_window_crm(
                    tr.items[win_start:pos], tr.n, float(params.theta),
                    top_frac=SMOKE_TOP_FRAC)
                prev = cliques_mod.generate_cliques(
                    prev, prev_crm, crm, tr.n, int(params.omega),
                    float(params.gamma))
                prev_crm = crm
                win_start = pos
                t_now = float(tr.times[pos])
                while next_cg <= t_now:
                    next_cg += t_cg
                continue
            pos = cut

    host_walk()                          # warm caches
    host = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        host_walk()
        host = min(host, time.perf_counter() - t0)
    host_pw = host / nbd

    perf = {
        "n": PERF_N_ITEMS,
        "windows": nbd,
        "compact_h": int(sched.h),
        "compact_us_per_window": round(compact_pw * 1e6),
        "dense_us_per_window": round(dense_pw * 1e6),
        "host_us_per_window": round(host_pw * 1e6),
        "speedup_vs_dense": round(dense_pw / max(compact_pw, 1e-12), 1),
        "layouts_agree": bool(np.array_equal(of_c, of_d)
                              and np.array_equal(ofs_c, ofs_d)),
    }
    payload = {}
    path = os.path.join(RESULTS_DIR, "BENCH_cgm.json")
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
    payload["compact_vs_dense_vs_host"] = perf
    save_json("BENCH_cgm", payload)
    return perf


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="device-CGM vs cliques_ref oracle gate (CI)")
    if ap.parse_args().smoke:
        smoke()
    else:
        main()
