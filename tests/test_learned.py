"""Learned cache-policy subsystem (featurizer / trainer / serving).

Contracts under test:

* featurizer twins — ``features_np`` and ``features_jnp`` agree to 1e-12
  at f64, and ``forward_np``/``forward_jnp`` score identically;
* schema freeze — params carry ``FEATURE_SCHEMA_VERSION``; serving and
  checkpoint loading refuse a mismatched schema loudly;
* warm start — with no trained params the ``learned`` policy reproduces
  the TTL baseline's keep decisions (and costs) EXACTLY;
* compile budget — ``train_policy`` stays within <= 2 traced compiles
  per call (``TRAIN_TRACES``, the SCAN_TRACES pattern) and a same-shape
  retrain compiles NOTHING;
* backend parity — trained params serve through numpy and jax replay at
  1e-9, on table1 AND heterogeneous cost models;
* snapshots — mid-stream ``CacheSession`` and ``LiveServingEngine``
  snapshot/restore resume bit-identically (the learned stats + params
  travel in the policy state);
* checkpoints — ``save_learned_params``/``load_learned_params``
  round-trip through ``repro.checkpoint`` exactly;
* training value (slow) — hindsight training beats ``no_packing`` on a
  held-out regime-shift trace, the fig11 acceptance gate in miniature.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import CacheEnvironment, CacheSession, CostParams, \
    get_policy, run_policy
from repro.learned import (
    FEATURE_NAMES,
    FEATURE_SCHEMA_VERSION,
    LearnedParams,
    LearnedPolicy,
    TrainConfig,
    features_jnp,
    features_np,
    forward_np,
    hindsight_windows,
    init_params,
    init_stats,
    load_learned_params,
    save_learned_params,
    train_policy,
    update_stats,
    warm_params,
)
from repro.learned.model import forward_jnp
from repro.serving import LiveServingEngine
from repro.traces import SynthConfig, synth_trace

PARAMS = CostParams(rho=4.0)       # keep/evict economics actually bite
T_CG = 12.0
INT_FIELDS = ("n_requests", "n_item_requests", "n_misses", "n_hits",
              "items_transferred")
FLOAT_FIELDS = ("transfer", "caching", "keepalive_rent", "total")


def _trace(n_requests=2500, seed=3, profile="regime_shift",
           size_dist="unit"):
    return synth_trace(SynthConfig(
        kind="netflix", n_items=60, n_servers=12, n_requests=n_requests,
        t_max=0.1 * n_requests, bundle_cover=1.0, bundle_zipf=0.7,
        server_affinity=2, load_profile=profile,
        load_strength=0.25 if profile == "regime_shift" else 0.8,
        load_peak=0.4, seed=seed, size_dist=size_dist))


def assert_same_costs(ref, got, exact=False):
    a, b = ref.as_dict(), got.as_dict()
    for f in INT_FIELDS:
        assert a[f] == b[f], f"{f}: {a[f]} != {b[f]}"
    for f in FLOAT_FIELDS:
        if exact:
            assert a[f] == b[f], f"{f}: {a[f]} != {b[f]}"
        else:
            assert np.isclose(a[f], b[f], rtol=1e-9, atol=1e-9), \
                f"{f}: {a[f]} != {b[f]}"


@pytest.fixture(scope="module")
def trace():
    return _trace()


@pytest.fixture(scope="module")
def trained(trace):
    return train_policy(trace, t_cg=T_CG, params=PARAMS,
                        cfg=TrainConfig(steps=60, batch=128))


# ---------------------------------------------------------------------------
# featurizer: numpy / jnp twins, schema freeze
# ---------------------------------------------------------------------------
def test_features_np_jnp_parity():
    import jax

    rng = np.random.default_rng(0)
    n, dt, t_cg = 40, 4.0, 12.0
    stats = init_stats(n, dt)
    for w in range(3):
        counts = rng.poisson(1.5, n).astype(np.float64)
        update_stats(stats, counts, 10.0 * (w + 1), t_cg)
    co_deg = rng.integers(0, 6, n).astype(np.float64)
    sizes = np.exp(rng.normal(0, 0.5, n))
    csz = rng.integers(1, 5, n).astype(np.float64)
    x_np = features_np(counts, co_deg, stats, sizes, csz, 30.0, dt, t_cg)
    with jax.enable_x64(True):
        x_j = np.asarray(features_jnp(
            counts, co_deg, stats, sizes, csz, 30.0, dt, t_cg))
    assert x_np.shape == (n, len(FEATURE_NAMES))
    np.testing.assert_allclose(x_j, x_np, rtol=1e-12, atol=1e-12)


def test_forward_np_jnp_parity():
    import jax

    rng = np.random.default_rng(1)
    lp = init_params(seed=7)
    X = rng.normal(0, 1, (50, lp.n_features))
    s_np = forward_np(lp, X)
    with jax.enable_x64(True):
        s_j = np.asarray(forward_jnp(lp.w, lp.mu, lp.sd, X))
    np.testing.assert_allclose(s_j, s_np, rtol=1e-12, atol=1e-12)


def test_forward_refuses_schema_mismatch():
    lp = init_params(seed=0)
    lp.schema = FEATURE_SCHEMA_VERSION + 1
    with pytest.raises(ValueError, match="schema"):
        forward_np(lp, np.zeros((3, lp.n_features)))


# ---------------------------------------------------------------------------
# warm start == TTL baseline, exactly
# ---------------------------------------------------------------------------
def test_warm_start_matches_ttl_exactly(trace):
    ref = run_policy(get_policy("ttl", params=PARAMS, t_cg=T_CG), trace)
    got = run_policy(get_policy("learned", params=PARAMS, t_cg=T_CG), trace)
    assert got.policy == "learned"
    assert_same_costs(ref.costs, got.costs, exact=True)


# ---------------------------------------------------------------------------
# hindsight labels
# ---------------------------------------------------------------------------
def test_hindsight_windows_shapes_and_weights(trace):
    X, y, w = hindsight_windows(trace, t_cg=T_CG, params=PARAMS)
    assert X.shape[1] == len(FEATURE_NAMES)
    assert X.shape[0] == y.shape[0] == w.shape[0]
    assert X.shape[0] > 0 and X.shape[0] % trace.n == 0
    assert set(np.unique(y)) <= {0.0, 1.0}
    assert (w >= 0).all() and w.sum() > 0
    # items never accessed next window have zero weight (cost-irrelevant)
    assert (w == 0).any()


def test_train_degenerate_trace_returns_warm_start():
    tiny = _trace(n_requests=5)        # a single window: nothing to label
    lp = train_policy(tiny, t_cg=1e9, params=PARAMS)
    ref = warm_params(PARAMS.lam, PARAMS.mu, 1e9, 1.0)
    np.testing.assert_array_equal(lp.w["w_lin"], ref.w["w_lin"])
    np.testing.assert_array_equal(lp.w["b"], ref.w["b"])


# ---------------------------------------------------------------------------
# compile budget (the SCAN_TRACES-style ratchet)
# ---------------------------------------------------------------------------
def test_train_compile_budget(trace):
    import repro.learned.train as lt

    cfg = TrainConfig(steps=40, batch=64)
    t0 = lt.TRAIN_TRACES
    train_policy(trace, t_cg=T_CG, params=PARAMS, cfg=cfg)
    assert lt.TRAIN_TRACES - t0 <= 2
    t1 = lt.TRAIN_TRACES
    # same shapes (same trace length bucket + config): zero new compiles
    train_policy(_trace(seed=4), t_cg=T_CG, params=PARAMS, cfg=cfg)
    assert lt.TRAIN_TRACES == t1


# ---------------------------------------------------------------------------
# backend parity with trained params: table1 + heterogeneous
# ---------------------------------------------------------------------------
def test_trained_policy_backend_parity_table1(trace, trained):
    mk = lambda: get_policy("learned", params=PARAMS, t_cg=T_CG,
                            learned=trained)
    ref = run_policy(mk(), trace)
    got = run_policy(mk(), trace, backend="jax")
    assert_same_costs(ref.costs, got.costs)


def test_trained_policy_backend_parity_heterogeneous():
    tr = _trace(size_dist="lognormal")
    env = CacheEnvironment.skewed(
        tr.n, tr.m, PARAMS, price_sigma=0.8, seed=1)
    env = CacheEnvironment.resolve(env, tr, PARAMS)
    lp = train_policy(tr, env=env, t_cg=T_CG, params=PARAMS,
                      cfg=TrainConfig(steps=40, batch=64),
                      cost_model="heterogeneous")
    mk = lambda: get_policy("learned", params=PARAMS, t_cg=T_CG,
                            learned=lp, env=env,
                            cost_model="heterogeneous")
    ref = run_policy(mk(), tr)
    got = run_policy(mk(), tr, backend="jax")
    assert_same_costs(ref.costs, got.costs)


# ---------------------------------------------------------------------------
# snapshots: CacheSession + LiveServingEngine, bitwise
# ---------------------------------------------------------------------------
def test_session_snapshot_restores_bitwise(trace, trained):
    mk = lambda: CacheSession(
        get_policy("learned", params=PARAMS, t_cg=T_CG, learned=trained),
        trace.n, trace.m)
    cut = trace.n_requests // 2
    base = mk()
    base.feed(trace.items, trace.servers, trace.times)

    first = mk()
    first.feed(trace.items[:cut], trace.servers[:cut], trace.times[:cut])
    second = mk().restore(first.snapshot())
    second.feed(trace.items[cut:], trace.servers[cut:], trace.times[cut:])
    assert_same_costs(base.costs, second.costs, exact=True)
    np.testing.assert_array_equal(second.engine.state.E, base.engine.state.E)
    np.testing.assert_array_equal(
        second.policy.item_keep(), base.policy.item_keep())


def test_live_engine_parity_and_snapshot(trace, trained):
    mk = lambda: get_policy("learned", params=PARAMS, t_cg=T_CG,
                            learned=trained)
    ref = run_policy(mk(), trace)

    eng = LiveServingEngine(mk(), trace.n, trace.m, chunk_size=512)
    eng.feed(trace.items, trace.servers, trace.times)
    eng.drain()
    assert_same_costs(ref.costs, eng.costs)

    cut = trace.n_requests // 2
    first = LiveServingEngine(mk(), trace.n, trace.m, chunk_size=512)
    first.feed(trace.items[:cut], trace.servers[:cut], trace.times[:cut])
    snap = first.snapshot()           # mid-stream: pending rides along
    second = LiveServingEngine(mk(), trace.n, trace.m,
                               chunk_size=512).restore(snap)
    second.feed(trace.items[cut:], trace.servers[cut:], trace.times[cut:])
    second.drain()
    assert_same_costs(eng.costs, second.costs, exact=True)


# ---------------------------------------------------------------------------
# checkpoint round-trip
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path, trained):
    d = str(tmp_path / "ckpt")
    save_learned_params(trained, d, step=3)
    back = load_learned_params(d)
    assert back.schema == trained.schema
    assert back.feature_names == FEATURE_NAMES
    for k in ("w_lin", "b", "w_in", "w_out"):
        np.testing.assert_array_equal(back.w[k], trained.w[k])
    for k, v in trained.w["trunk"].items():
        np.testing.assert_array_equal(back.w["trunk"][k], v)
    np.testing.assert_array_equal(back.mu, trained.mu)
    np.testing.assert_array_equal(back.sd, trained.sd)
    # decisions survive the round trip bit-for-bit
    X = np.random.default_rng(5).normal(0, 1, (64, trained.n_features))
    np.testing.assert_array_equal(forward_np(back, X),
                                  forward_np(trained, X))


def test_checkpoint_refuses_schema_mismatch(tmp_path, trained):
    d = str(tmp_path / "ckpt")
    stale = LearnedParams.from_tree(trained.tree())
    stale.schema = FEATURE_SCHEMA_VERSION + 7
    save_learned_params(stale, d, step=0)
    with pytest.raises(ValueError, match="schema"):
        load_learned_params(d)
    with pytest.raises(FileNotFoundError):
        load_learned_params(str(tmp_path / "nowhere"))


# ---------------------------------------------------------------------------
# training value: the fig11 acceptance gate in miniature
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_trained_beats_no_packing_on_held_out_regime_shift():
    train_tr = _trace(seed=200)
    lp = train_policy(train_tr, t_cg=T_CG, params=PARAMS)
    eval_tr = _trace(seed=101)
    learned = run_policy(
        get_policy("learned", params=PARAMS, t_cg=T_CG, learned=lp),
        eval_tr).total
    nop = run_policy(get_policy("no_packing", params=PARAMS), eval_tr).total
    pc = run_policy(
        get_policy("packcache", params=PARAMS, t_cg=T_CG, top_frac=1.0),
        eval_tr).total
    assert learned < nop               # strictly beats the no-cache baseline
    assert learned < pc                # ... and a non-AKPC packing baseline
