"""Shared cases for the streaming-engine parity tests.

Every registered policy under the three JAX-expressible cost models, on
the 60-item, 4,000-request synthetic trace of ``test_sweep.py`` (its
lognormal-size twin for the size-aware models).  The reference is the
numpy ``run_policy`` of the same policy on the same trace; a streaming
engine matches it when the integer counters are exact, the cost sums
agree at 1e-9 relative and the installed partition is the same.
"""
import functools

import numpy as np

from repro.core import (
    CacheEnvironment,
    CacheSession,
    CostParams,
    get_policy,
    run_policy,
)
from repro.traces import SynthConfig, synth_trace

PARAMS = CostParams()
T_CG = 0.73            # never divides the chunk grid: windows split chunks
POLICIES = ("no_packing", "ttl", "learned", "packcache", "dp_greedy",
            "akpc", "akpc_no_acm", "akpc_base")
COST_MODELS = ("table1", "heterogeneous", "tiered")

INT_FIELDS = ("n_requests", "n_item_requests", "n_misses", "n_hits",
              "items_transferred")
FLOAT_FIELDS = ("transfer", "caching", "keepalive_rent", "total")


@functools.lru_cache(maxsize=None)
def trace(size_dist="unit"):
    return synth_trace(SynthConfig(
        kind="netflix", n_items=60, n_servers=12, n_requests=4000,
        t_max=30.0, bundle_cover=1.0, bundle_zipf=0.7, seed=3,
        size_dist=size_dist))


@functools.lru_cache(maxsize=None)
def case(model):
    """(trace, env) of one cost model: unit sizes for ``table1``, the
    skewed per-server prices of ``test_sweep.py`` for ``heterogeneous``,
    the trace's own sizes for ``tiered``."""
    if model == "table1":
        return trace(), None
    tr = trace("lognormal")
    env = None
    if model == "heterogeneous":
        env = CacheEnvironment.skewed(tr.n, tr.m, PARAMS, price_sigma=0.8,
                                      seed=1)
    return tr, CacheEnvironment.resolve(env, tr, PARAMS)


def _kwargs(name, model):
    _, env = case(model)
    kw = {"params": PARAMS, "cost_model": model, "env": env}
    if name in ("packcache", "akpc", "akpc_no_acm", "akpc_base"):
        kw.update(t_cg=T_CG, top_frac=1.0)
    if name in ("ttl", "learned"):
        kw.update(t_cg=T_CG)
    if name == "dp_greedy":
        kw.update(top_frac=1.0, partition=_dp_partition(model))
    return kw


@functools.lru_cache(maxsize=None)
def _dp_partition(model):
    """dp_greedy is offline: the streaming engines get its full-trace
    pairing up front, as a deployment would mine it from history."""
    tr, env = case(model)
    pol = get_policy("dp_greedy", params=PARAMS, cost_model=model, env=env,
                     top_frac=1.0)
    pol.bind(tr.n, tr.m)
    return pol.initial_partition(tr)


def policy(name, model):
    """A fresh policy object (streaming engines mutate their policy)."""
    return get_policy(name, **_kwargs(name, model))


@functools.lru_cache(maxsize=None)
def reference(name, model):
    """(numpy run_policy result, canonical partition of a numpy session
    fed the whole trace) — the installed partition run_policy leaves."""
    tr, _ = case(model)
    ref = run_policy(policy(name, model), tr)
    sess = CacheSession(policy(name, model), tr.n, tr.m)
    sess.feed_trace(tr)
    assert_same_costs(ref.costs, sess.costs)
    assert np.array_equal(sess.partition.sizes(), ref.clique_sizes)
    return ref, sess.partition.canonical()


def slices(n_requests, seed=0, lo=0, hi=None):
    """Ragged arrival slices [a, b) covering [lo, hi)."""
    rng = np.random.default_rng(seed)
    hi = n_requests if hi is None else hi
    while lo < hi:
        k = min(int(rng.integers(1, 300)), hi - lo)
        yield lo, lo + k
        lo += k


def assert_same_costs(ref, got, rtol=1e-9):
    a = ref.as_dict() if not isinstance(ref, dict) else ref
    b = got.as_dict() if not isinstance(got, dict) else got
    for f in INT_FIELDS:
        assert a[f] == b[f], f"{f}: {a[f]} != {b[f]}"
    for f in FLOAT_FIELDS:
        if rtol == 0.0:
            assert a[f] == b[f], f"{f}: {a[f]} != {b[f]}"
        else:
            assert np.isclose(a[f], b[f], rtol=rtol, atol=1e-9), \
                f"{f}: {a[f]} != {b[f]}"
