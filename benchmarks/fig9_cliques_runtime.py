"""Fig. 9 — (a) clique-size distribution across AKPC variants,
(b) clique-generation wall time vs number of data items (up to 10k).

``--smoke`` (CI) runs only the (b) runtime sweep on a small item grid and
fails loudly when the vectorized CGM regresses to at or past the pre-PR-3
scalar implementation's wall time (``PRE_VECTORIZATION_BASELINE``).
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from .common import N_SWEEP, emit, get_trace, save_json, t_cg_for
from repro.core import CostParams, get_policy, run_policy
from repro.core.crm import build_window_crm
from repro.core.cliques import generate_cliques
from repro.traces import SynthConfig, synth_trace

RUNTIME_ITEMS = [100, 1000, 4000, 10000]
SMOKE_ITEMS = [1000, 4000]

#: catalog sizes for the device-resident CGM timing (BENCH_cgm.json).
#: The compact hot-space carry (DESIGN.md §15) lifted the old 256-item
#: auto-routing ceiling, so this sweep now reaches fig9-scale catalogs.
DEVICE_CGM_ITEMS = [64, 1000, 4000]

#: wall seconds of this same sweep under the pre-vectorization (scalar)
#: CGM, recorded before PR 3 on the reference container — the regression
#: bar for --smoke and the denominator of the reported speedups
PRE_VECTORIZATION_BASELINE = {100: 0.0045, 1000: 0.0232, 4000: 0.1373,
                              10000: 0.6229}


def _runtime_trace(n: int):
    return synth_trace(SynthConfig(
        kind="spotify", n_items=n, n_servers=100, n_requests=20000,
        t_max=20.0, bundle_cover=1.0, bundle_zipf=0.7, seed=0))


def _time_clique_gen(n: int, reps: int = 5) -> tuple[float, int]:
    """One clique-generation event over a 20k-request window on n items.

    Best of ``reps`` repetitions — a single cold pass mostly measures
    allocator/page-cache warmup once the event itself is millisecond-scale.
    ``top_frac_of="catalog"`` pins the pre-PR-3 hot-set semantics so the
    workload is identical to the one PRE_VECTORIZATION_BASELINE timed.
    """
    tr = _runtime_trace(n)
    dt = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        crm = build_window_crm(tr.items, n, theta=0.2, top_frac=0.1,
                               top_frac_of="catalog")
        part = generate_cliques(None, None, crm, n, omega=5, gamma=0.85)
        dt = min(dt, time.perf_counter() - t0)
    return dt, sum(1 for c in part.cliques if len(c) > 1)


def _time_clique_gen_oracle(n: int) -> float:
    """Same event through the frozen scalar oracle (the --smoke yardstick:
    timed on the same machine, so the gate is hardware-independent)."""
    from repro.core import cliques_ref

    tr = _runtime_trace(n)
    t0 = time.perf_counter()
    crm = build_window_crm(tr.items, n, theta=0.2, top_frac=0.1,
                           top_frac_of="catalog")
    cliques_ref.generate_cliques(None, None, crm, n, omega=5, gamma=0.85)
    return time.perf_counter() - t0


def _device_cgm_trace(n: int):
    return synth_trace(SynthConfig(
        kind="spotify", n_items=n, n_servers=20, n_requests=8000,
        t_max=20.0, bundle_cover=1.0, bundle_zipf=0.7, seed=0))


def _time_device_cgm(n: int) -> dict:
    """Warm wall time of a fully device-resident windowed replay (CGM
    inside the jit'd scan, DESIGN.md §11) vs the host-CGM jax path on the
    same trace — the PR-6 seam recorded in BENCH_cgm.json.

    Warm times (one compile pass first): the steady state every sweep
    lane pays.
    """
    import os

    from repro.core.engine_jax import run_policy_jax

    tr = _device_cgm_trace(n)
    params = CostParams()
    t_cg = t_cg_for(tr, params)

    def timed(mode: str) -> tuple[float, int]:
        old = os.environ.get("REPRO_JAX_CGM")
        os.environ["REPRO_JAX_CGM"] = mode
        try:
            run_policy_jax(
                get_policy("akpc", params=params, t_cg=t_cg,
                           top_frac=0.5), tr)        # compile pass
            t0 = time.perf_counter()
            res = run_policy_jax(
                get_policy("akpc", params=params, t_cg=t_cg,
                           top_frac=0.5), tr)
            return time.perf_counter() - t0, res.n_windows
        finally:
            if old is None:
                os.environ.pop("REPRO_JAX_CGM", None)
            else:
                os.environ["REPRO_JAX_CGM"] = old

    dev, n_windows = timed("force")
    host, _ = timed("off")
    return {
        "device_seconds": round(dev, 4),
        "host_jax_seconds": round(host, 4),
        "n_windows": n_windows,
        "device_us_per_window": round(dev / max(1, n_windows) * 1e6),
    }


def main(smoke: bool = False) -> list[tuple]:
    rows, payload = [], {"dist": {}, "runtime": {}}
    payload["runtime_baseline_pre_vectorization"] = {
        str(k): v for k, v in PRE_VECTORIZATION_BASELINE.items()
    }
    params = CostParams()

    # (b) clique-generation runtime: one window over n items (top-10% mined).
    # Timed before the (a) policy sweeps — their replay allocations fragment
    # the arena enough to skew millisecond-scale timings.
    regressions = []
    for n in (SMOKE_ITEMS if smoke else RUNTIME_ITEMS):
        dt, n_cliques = _time_clique_gen(n)
        base = PRE_VECTORIZATION_BASELINE.get(n)
        speedup = round(base / dt, 1) if base else None
        payload["runtime"][n] = round(dt, 4)
        if base:
            payload.setdefault("speedup_vs_pre_vectorization", {})[n] = speedup
        rows.append((f"fig9b/items={n}", int(dt * 1e6),
                     f"seconds={round(dt,4)};cliques={n_cliques};"
                     f"speedup={speedup}"))
        if smoke:
            # gate against the scalar oracle ON THIS MACHINE — absolute
            # baseline constants would misfire on slow/loaded CI runners
            oracle = _time_clique_gen_oracle(n)
            payload.setdefault("runtime_scalar_oracle", {})[n] = round(oracle, 4)
            if dt >= oracle:
                regressions.append(
                    f"items={n}: vectorized {dt:.4f}s >= scalar oracle "
                    f"{oracle:.4f}s on this machine"
                )

    if not smoke:
        for kind in ("netflix", "spotify"):
            tr = get_trace(kind, N_SWEEP)
            t_cg = t_cg_for(tr, params)
            variants = {
                name: run_policy(
                    get_policy(name, params=params, t_cg=t_cg, top_frac=1.0), tr)
                for name in ("akpc", "akpc_no_acm", "akpc_base")
            }
            for name, res in variants.items():
                sizes = np.concatenate(res.size_history) if res.size_history else np.array([])
                hist = np.bincount(sizes.astype(int), minlength=11)[:11].tolist() if sizes.size else []
                mean = float(sizes.mean()) if sizes.size else 0.0
                payload["dist"].setdefault(kind, {})[name] = {
                    "hist": hist, "mean": round(mean, 2)}
                rows.append((f"fig9a/{kind}/{name}", 0,
                             f"mean_size={round(mean,2)};hist={hist}"))

    # device-resident CGM timing (PR 6): the windowed replay with clique
    # generation inside the scan vs the host-CGM jax path, per catalog size
    cgm_items = {}
    for n in DEVICE_CGM_ITEMS:
        row = _time_device_cgm(n)
        cgm_items[n] = row
        rows.append((
            f"bench_cgm/items={n}", int(row["device_seconds"] * 1e6),
            f"device={row['device_seconds']}s;"
            f"host_jax={row['host_jax_seconds']}s;"
            f"windows={row['n_windows']};"
            f"us_per_window={row['device_us_per_window']}"))
    if cgm_items:
        # merge-write: fig7's compact_vs_dense_vs_host breakdown lives in
        # the same file, so preserve whatever keys are already there
        import json
        import os

        from .common import RESULTS_DIR

        cgm_payload = {}
        path = os.path.join(RESULTS_DIR, "BENCH_cgm.json")
        if os.path.exists(path):
            with open(path) as f:
                cgm_payload = json.load(f)
        cgm_payload.update({"trace": "spotify/8000req", "items": cgm_items})
        save_json("BENCH_cgm", cgm_payload)

    save_json("fig9_cliques_runtime", payload)
    emit(rows)
    if regressions:
        print("CGM RUNTIME REGRESSION:\n  " + "\n  ".join(regressions),
              file=sys.stderr)
        sys.exit(1)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small item sweep + regression gate (CI)")
    main(smoke=ap.parse_args().smoke)
