"""Fig. 11 — NEW scenario axis beyond the paper: non-stationary request
load (Carlsson & Eager's time-varying arrival model, arXiv 1803.03914)
stress-ranking EVERY registered policy, including the ``learned``
keep-or-not policy trained on a held-out trace of the same scenario.

Four load profiles x two pricing models, every policy, three eval-seed
trace shards per point — all replayed in ONE ``SweepEngine`` grid (the
shard axis rides as extra vmap lanes, so per-scenario dispersion CIs come
back at near-zero marginal device cost).  Per (scenario, model) the
payload records the merged totals, the per-shard ``shard_stats`` and the
resulting policy RANKING; results land in ``BENCH_learned.json``.

Scenario economics: ``rho = 4.0`` (the paper's fig6 sensitivity axis)
widens the prepaid-rent stake of every keep decision — the regime where
keep-or-not policies (ttl, learned) separate from always-keep packers.
``load_strength`` per profile is tuned so the arrival-rate swing actually
moves item economics across T_CG windows (regime_shift drops the rate to
0.25x at 40% of the horizon; flash_crowd spikes 4x).

``--smoke`` is the CI gate (small traces, regime_shift/table1 only):

* the trained ``learned`` policy must STRICTLY beat ``no_packing`` AND at
  least one non-AKPC baseline (ttl / packcache / dp_greedy);
* numpy vs jax replay of the trained policy agrees to 1e-9;
* training stays within its compile budget (TRAIN_TRACES delta <= 2 per
  ``train_policy`` call).
"""
from __future__ import annotations

import sys

import numpy as np

from .common import emit, save_json, t_cg_for
from repro.core import (
    CacheEnvironment, CostParams, SweepEngine, SweepPoint, list_policies,
    run_policy,
)
from repro.core.engine_jax import JAX_COST_MODELS
from repro.learned import train_policy
from repro.traces import SynthConfig, synth_trace

#: load profile -> load_strength (diurnal amplitude / crowd height x base /
#: regime rate ratio — see repro.traces.synthetic.load_rate)
SCENARIOS = {
    "stationary": 0.0,
    "diurnal": 0.8,
    "flash_crowd": 4.0,
    "regime_shift": 0.25,
}
MODELS = ("table1", "heterogeneous")
#: canonical registry names (aliases like packcache2 resolve to these)
POLICIES = ("no_packing", "ttl", "dp_greedy", "packcache",
            "akpc_base", "akpc_no_acm", "akpc", "learned")
N_ITEMS, N_SERVERS = 60, 12
TRAIN_SEED = 200
EVAL_SEEDS = (101, 102, 103)
#: t_max = 0.1 * n_requests: ~8.3 requests per server per unit time over
#: 60 items — hot-item revisit gaps straddle the rho=4 TTL, so keep/evict
#: is a real decision (denser: everything stays fresh; sparser: nothing).
TIME_PER_REQUEST = 0.1


def stress_trace(profile: str, seed: int, n_requests: int):
    """One non-stationary trace; content is seed-determined, only arrival
    times differ across profiles (inverse-CDF warp of the same draws)."""
    return synth_trace(SynthConfig(
        kind="netflix", n_items=N_ITEMS, n_servers=N_SERVERS,
        n_requests=n_requests, t_max=TIME_PER_REQUEST * n_requests,
        bundle_cover=1.0, bundle_zipf=0.7, server_affinity=2,
        load_profile=profile, load_strength=SCENARIOS[profile],
        load_peak=0.4, seed=seed,
    ))


def env_for(cost_model: str, params: CostParams) -> CacheEnvironment | None:
    """Pricing environment per model: homogeneous Table-I, or skewed
    per-server prices + lognormal item sizes for ``heterogeneous``."""
    if cost_model == "heterogeneous":
        return CacheEnvironment.skewed(
            N_ITEMS, N_SERVERS, params, price_sigma=0.8, size_sigma=0.5,
            seed=1)
    return None


def policy_kwargs(name: str, t_cg: float, lp) -> dict:
    if name == "no_packing":
        return {}
    if name == "dp_greedy":
        return {}
    if name == "learned":
        return dict(t_cg=t_cg, learned=lp)
    return dict(t_cg=t_cg)


def run_grid(n_requests: int, eval_seeds=EVAL_SEEDS,
             scenarios=tuple(SCENARIOS), models=MODELS,
             policies=POLICIES) -> dict:
    """Train per (scenario, model), then rank ALL policies over the
    eval-seed shard axis in ONE SweepEngine call."""
    assert set(policies) <= {  # every canonical registry policy is ranked
        name for name in list_policies()}, (policies, list_policies())
    params = CostParams(rho=4.0)
    backend = ("jax" if all(m in JAX_COST_MODELS for m in models)
               else "numpy")

    pts, keys = [], []
    for cm in models:
        env = env_for(cm, params)
        for profile in scenarios:
            train_tr = stress_trace(profile, TRAIN_SEED, n_requests)
            tcg = t_cg_for(train_tr, params, env=env, cost_model=cm)
            lp = train_policy(train_tr, env=env, t_cg=tcg, params=params,
                              cost_model=cm)
            shards = tuple(stress_trace(profile, s, n_requests)
                           for s in eval_seeds)
            for name in policies:
                pts.append(SweepPoint(
                    name, shards,
                    dict(params=params, env=env, cost_model=cm,
                         **policy_kwargs(name, tcg, lp)),
                    tag=f"{profile}/{cm}"))
                keys.append((profile, cm, name))

    res = SweepEngine(backend=backend).run(pts)

    payload: dict = {
        "n_requests": n_requests, "rho": params.rho,
        "eval_seeds": list(eval_seeds), "backend": backend, "grid": {},
    }
    for (profile, cm, name), r in zip(keys, res):
        cell = payload["grid"].setdefault(f"{profile}/{cm}", {})
        cell[name] = {
            "total": r.costs.total, "transfer": r.costs.transfer,
            "caching": r.costs.caching, "shard_stats": r.shard_stats,
        }
    for key, cell in payload["grid"].items():
        ranking = sorted(policies, key=lambda p: cell[p]["total"])
        cell["ranking"] = ranking
        cell["learned_rank"] = ranking.index("learned") + 1
        cell["learned_vs_no_packing_saving_pct"] = round(
            100.0 * (1.0 - cell["learned"]["total"]
                     / cell["no_packing"]["total"]), 2)
    return payload


def smoke() -> int:
    """CI gate on the smallest scenario where the learned ranking signal
    is stable: regime_shift x table1 (see module docstring)."""
    import repro.learned.train as lt

    n_requests, eval_seeds = 2500, (101, 102)
    params = CostParams(rho=4.0)
    train_tr = stress_trace("regime_shift", TRAIN_SEED, n_requests)
    tcg = t_cg_for(train_tr, params, cost_model="table1")

    traces0 = lt.TRAIN_TRACES
    lp = train_policy(train_tr, t_cg=tcg, params=params)
    n_compiles = lt.TRAIN_TRACES - traces0
    print(f"fig11 --smoke: train compiles={n_compiles}")
    if n_compiles > 2:
        print("FAIL: train_policy exceeded its compile budget (<= 2)")
        return 1

    shards = tuple(stress_trace("regime_shift", s, n_requests)
                   for s in eval_seeds)
    rivals = ("ttl", "packcache", "dp_greedy")
    pts = [SweepPoint(name, shards,
                      dict(params=params,
                           **policy_kwargs(name, tcg, lp)))
           for name in ("no_packing", *rivals, "learned")]
    res = {p.policy: r for p, r in zip(pts, SweepEngine().run(pts))}
    totals = {k: r.costs.total for k, r in res.items()}
    print("fig11 --smoke: " + " ".join(
        f"{k}={v:.0f}" for k, v in sorted(totals.items(),
                                          key=lambda kv: kv[1])))
    if totals["learned"] >= totals["no_packing"]:
        print("FAIL: trained policy does not beat no_packing on the "
              "regime-shift stress trace")
        return 1
    if not any(totals["learned"] < totals[r] for r in rivals):
        print(f"FAIL: trained policy beats none of {rivals}")
        return 1

    from repro.core import get_policy

    tr = shards[0]
    t_np = run_policy(
        get_policy("learned", params=params, t_cg=tcg, learned=lp),
        tr).costs.total
    t_jx = run_policy(
        get_policy("learned", params=params, t_cg=tcg, learned=lp),
        tr, backend="jax").costs.total
    print(f"fig11 --smoke: parity numpy={t_np:.9f} jax={t_jx:.9f}")
    if abs(t_np - t_jx) > 1e-9:
        print("FAIL: numpy/jax replay of the learned policy disagree")
        return 1
    print("OK")
    return 0


def main() -> list[tuple]:
    payload = run_grid(int(sys.argv[sys.argv.index("--requests") + 1])
                       if "--requests" in sys.argv else 6000)
    rows = []
    for key, cell in payload["grid"].items():
        rows.append((
            f"fig11/{key}", 0,
            "rank=" + ">".join(cell["ranking"])
            + f";learned_saving={cell['learned_vs_no_packing_saving_pct']}%",
        ))
    save_json("BENCH_learned", payload)
    emit(rows)
    return rows


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        sys.exit(smoke())
    main()
