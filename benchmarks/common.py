"""Shared benchmark machinery: trace cache, method runners, CSV emit.

Every figure benchmark replays the SAME seeded synthetic traces (paper
§V.A setup, see repro.traces.synthetic.paper_trace and EXPERIMENTS.md for
the deviation analysis vs the proprietary Kaggle dumps) through the method
set of Fig. 5, resolved from the unified policy registry
(``repro.core.get_policy`` / ``run_policy``):

  no_packing / dp_greedy (offline 2-pack) / packcache (online 2-pack) /
  akpc_base (w/o CS, w/o ACM) / akpc (proposed) / opt (lower bound)

Costs are reported relative to OPT (paper convention, OPT = 1).
"""
from __future__ import annotations

import functools
import json
import os
import time

import numpy as np

from repro.core import (
    CacheEnvironment, CostParams, get_cost_model, get_policy, opt_lower_bound,
    run_policy,
)
from repro.traces import paper_trace

RESULTS_DIR = os.environ.get("REPRO_RESULTS", "experiments/results")
N_REQUESTS = int(os.environ.get("REPRO_BENCH_REQUESTS", "150000"))
N_SWEEP = int(os.environ.get("REPRO_BENCH_SWEEP_REQUESTS", "40000"))
#: >1 splits each figure's request budget over per-seed trace replicas
#: (the SweepEngine trace-shard vmap axis) so figs report mean +- 95% CI
N_SHARDS = int(os.environ.get("REPRO_BENCH_SHARDS", "1"))

@functools.lru_cache(maxsize=8)
def get_trace(kind: str, n_requests: int, seed: int = 0):
    return paper_trace(kind, n_requests=n_requests, seed=seed)


@functools.lru_cache(maxsize=8)
def get_trace_shards(kind: str, n_requests: int, shards: int | None = None,
                     seed0: int = 0):
    """The figure workload as a trace-shard tuple (or one trace).

    ``shards`` defaults to ``REPRO_BENCH_SHARDS``; above 1 the request
    budget splits across per-seed replicas that replay as extra vmap
    lanes of one sweep call (``SweepPoint`` shard axis), so every
    ``run_method_grid`` entry gains ``shard_stats`` (mean +- 95% CI of
    the per-shard totals) at near-zero marginal device cost.  At the
    default 1 this IS ``get_trace`` — figure payloads stay bitwise."""
    k = N_SHARDS if shards is None else int(shards)
    if k <= 1:
        return get_trace(kind, n_requests, seed0)
    return tuple(
        paper_trace(kind, n_requests=max(1, n_requests // k),
                    seed=seed0 + i)
        for i in range(k))


def t_cg_for(trace, params: CostParams | None = None,
             env: CacheEnvironment | None = None,
             cost_model: str = "table1") -> float:
    """Clique-generation period: a small multiple of the cache TTL dt —
    long enough to observe co-access, short enough to track drift.
    (Regenerating much faster than dt churns partitions and loses cached
    presence; see EXPERIMENTS.md §Fig5 notes.)  The TTL comes from the
    registered cost model (max over servers under heterogeneous prices),
    not from CostParams internals."""
    if env is None:
        env = CacheEnvironment(trace.n, trace.m, params or CostParams())
    dt = float(get_cost_model(cost_model, env).dt().max())
    span = float(trace.times[-1] - trace.times[0])
    return float(min(max(0.3 * dt, span / 50.0), max(span / 4.0, 1e-6)))


def method_policies(params: CostParams, t_cg: float, top_frac: float) -> dict:
    """Fig.-5 method set as (registry name -> policy kwargs)."""
    return {
        "no_packing": {},
        "ttl": dict(t_cg=t_cg),
        "learned": dict(t_cg=t_cg),   # warm-start scorer (no trained params)
        "dp_greedy": dict(top_frac=top_frac),
        "packcache": dict(t_cg=t_cg, top_frac=top_frac),
        "akpc_base": dict(t_cg=t_cg, top_frac=top_frac),
        "akpc": dict(t_cg=t_cg, top_frac=top_frac),
    }


def _result_entry(res) -> dict:
    """One method's payload entry from a RunResult (shared by the serial
    run_methods and the sweep-backed run_method_grid, so both paths emit
    the identical JSON shape)."""
    entry = {
        "total": res.total,
        "transfer": res.costs.transfer,
        "caching": res.costs.caching,
        "seconds": round(res.wall_seconds, 2),
    }
    if (res.clique_sizes > 1).any():
        entry["clique_sizes"] = np.bincount(res.clique_sizes).tolist()
    if getattr(res, "shard_stats", None):
        entry["shard_stats"] = res.shard_stats
    return entry


def _maybe_add_opt(out: dict, trace, params, env, cost_model, methods) -> None:
    """Attach the OPT lower bound when requested and valid for the model.

    For a trace-shard tuple the bound is the SUM of per-shard bounds —
    the same aggregation ``SweepEngine`` applies to the policy costs, so
    opt-relative numbers stay comparable under sharding."""
    if methods is not None and "opt" not in methods:
        return
    from repro.core.baselines import OPT_BOUND_MODELS

    if cost_model not in OPT_BOUND_MODELS:
        # no valid lower bound of this form (e.g. tiered) — callers
        # compare against no_packing instead
        return
    t0 = time.perf_counter()
    shards = trace if isinstance(trace, (list, tuple)) else (trace,)
    totals = np.zeros(3, np.float64)
    for tr in shards:
        costs = opt_lower_bound(tr, params, env=env, cost_model=cost_model)
        totals += (costs.total, costs.transfer, costs.caching)
    out["opt"] = {
        "total": float(totals[0]),
        "transfer": float(totals[1]),
        "caching": float(totals[2]),
        "seconds": round(time.perf_counter() - t0, 2),
    }


def run_methods(trace, params: CostParams, methods=None, top_frac: float = 1.0,
                env: CacheEnvironment | None = None,
                cost_model: str = "table1"):
    """Returns {method: {total, transfer, caching, seconds}}.

    ``env``/``cost_model`` select the pricing scenario (default: the paper's
    homogeneous Table-I regime; fig10 passes heterogeneous environments).
    """
    # one resolution for policies AND the opt bound, so both price the
    # same scenario (threads trace.sizes into a price-only env)
    env = CacheEnvironment.resolve(env, trace, params)
    t_cg = t_cg_for(trace, params, env=env, cost_model=cost_model)
    out = {}
    for name, kw in method_policies(params, t_cg, top_frac).items():
        if methods is not None and name not in methods:
            continue
        res = run_policy(
            get_policy(name, params=params, env=env, cost_model=cost_model,
                       **kw),
            trace,
        )
        out[name] = _result_entry(res)
    _maybe_add_opt(out, trace, params, env, cost_model, methods)
    return out


def run_method_grid(grid: list[dict], backend: str | None = None,
                    layout=None) -> list[dict]:
    """Sweep MANY (trace, params, scenario) points in ONE vmapped call.

    Each grid entry takes the :func:`run_methods` keyword set
    (``trace`` required; ``params``, ``methods``, ``top_frac``, ``env``,
    ``cost_model`` optional, plus ``t_cg`` to OVERRIDE the derived
    clique-gen period — fig8's batch axis sweeps it directly) and each
    returned entry has the same
    ``{method: {total, transfer, caching, seconds}}`` shape — so the fig
    drivers swap a loop of ``run_methods`` calls for one
    ``run_method_grid`` call without changing their payloads.

    ``layout`` is a :class:`repro.core.state_layout.StateLayout` (or
    kind string) for the device state geometry; ``"bucketed"`` lets a
    mixed-(n, m) grid compile per bucket cohort instead of per point.

    All policy replays go through :class:`repro.core.SweepEngine`:
    scenarios sharing (trace x clique-gen hyperparameters) share one
    host schedule, and every group replays as one vmapped device scan
    (``REPRO_SWEEP_BACKEND=numpy`` restores the serial loop; it also
    engages when a cost model has no JAX formula, and the backend that
    ran is printed).  OPT lower bounds are closed-form and stay host-side.
    """
    from repro.core import SweepEngine, SweepPoint
    from repro.core.engine_jax import JAX_COST_MODELS

    if backend is None:
        backend = os.environ.get("REPRO_SWEEP_BACKEND", "") or "jax"
    if backend == "jax" and any(
            g.get("cost_model", "table1") not in JAX_COST_MODELS
            for g in grid):
        backend = "numpy"
    print(f"# run_method_grid: {len(grid)} grid entries on the {backend} "
          "backend", flush=True)

    pts, slots, resolved = [], [], []
    for gi, g in enumerate(grid):
        trace = g["trace"]
        # a tuple/list of traces is the shard axis (get_trace_shards):
        # scenario resolution reads the representative first shard
        tr0 = trace[0] if isinstance(trace, (list, tuple)) else trace
        params = g.get("params") or CostParams()
        env = CacheEnvironment.resolve(g.get("env"), tr0, params)
        cost_model = g.get("cost_model", "table1")
        methods = g.get("methods")
        t_cg = g.get("t_cg")
        if t_cg is None:
            t_cg = t_cg_for(tr0, params, env=env, cost_model=cost_model)
        resolved.append((trace, params, env, cost_model, methods))
        for name, kw in method_policies(
                params, t_cg, g.get("top_frac", 1.0)).items():
            if methods is not None and name not in methods:
                continue
            pts.append(SweepPoint(
                name, trace,
                dict(params=params, env=env, cost_model=cost_model, **kw)))
            slots.append(gi)

    res = SweepEngine(backend=backend, layout=layout).run(pts)
    out: list[dict] = [{} for _ in grid]
    for pt, gi, r in zip(pts, slots, res):
        out[gi][pt.policy] = _result_entry(r)

    for gi, (trace, params, env, cost_model, methods) in enumerate(resolved):
        _maybe_add_opt(out[gi], trace, params, env, cost_model, methods)
    return out


def relative_to_opt(res: dict, reference: str = "opt") -> dict:
    """Totals relative to ``reference`` (default: the OPT lower bound).

    run_methods omits "opt" for cost models without a valid bound (e.g.
    tiered pricing) — there, pick the reference EXPLICITLY, e.g.
    ``relative_to_opt(res, reference="no_packing")``, so opt-relative and
    baseline-relative numbers can never be confused."""
    if reference not in res:
        raise KeyError(
            f"no {reference!r} entry in results (no valid OPT bound for "
            'this cost model?); pass reference="no_packing" explicitly')
    base = res[reference]["total"]
    return {k: round(v["total"] / base, 4) for k, v in res.items()}


def emit(rows: list[tuple]) -> None:
    """CSV to stdout: name,us_per_call,derived."""
    for name, us, derived in rows:
        print(f"{name},{us},{derived}")


def save_json(name: str, payload) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path
