"""StateLayout (ISSUE 8): bucketed compilation + row-sharded device state.

Contracts under test:

* geometry — dense is the bitwise default (``layout=None`` everywhere);
  bucketed rounds (n, m) up to padding buckets with the dump row LAST;
  row_sharded pads rows to a shard multiple; ``is_dense_for`` gates the
  dense-only device-CGM path (``init_cgm_carry`` refuses otherwise);
* parity — every layout replays the SAME costs as the numpy engine at
  1e-9 (integers exact), including the n=1 edge and an n=10^4 catalog;
* cohort compilation — a mixed-(n, m) SweepEngine grid under a bucketed
  layout compiles once per bucket cohort, NOT once per point;
* round-trips — snapshots port freely dense<->bucketed (host state is
  dense (k, m) under every layout); a row-sharded snapshot restored
  into a row-sharded session refuses a mismatched shard count;
* pad_schedule — padding preserves the schedule's state geometry and
  the dump-row sentinel under every layout;
* mesh placement — on >= 4 devices (the CI multi-device lane sets
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4``), a
  row-sharded layout demonstrably spreads the state rows across the
  ``state_row`` mesh axis and still prices at 1e-9.
"""
import numpy as np
import pytest

from repro.core import CostParams, get_policy, run_policy
from repro.core import engine_jax as ej
from repro.core.engine_jax import run_policy_jax
from repro.core.session import CacheSession
from repro.core.state_layout import DENSE, StateLayout
from repro.core.sweep import SweepEngine, SweepPoint
from repro.traces import SynthConfig, synth_trace

jax = pytest.importorskip("jax")

PARAMS = CostParams()
INT_FIELDS = ("n_requests", "n_item_requests", "n_misses", "n_hits",
              "items_transferred")
FLOAT_FIELDS = ("transfer", "caching", "keepalive_rent", "total")

BUCKETED = StateLayout(kind="bucketed", row_bucket=16, col_bucket=8)
SHARDED3 = StateLayout(kind="row_sharded", shards=3)


def _trace(n_items=40, n_servers=10, n_requests=2500, seed=5, **kw):
    kw.setdefault("bundle_cover", 1.0)
    kw.setdefault("bundle_zipf", 0.7)
    return synth_trace(SynthConfig(
        kind="netflix", n_items=n_items, n_servers=n_servers,
        n_requests=n_requests, t_max=20.0, seed=seed, **kw))


def _policy(name="akpc", **kw):
    if name in ("akpc", "ttl", "packcache"):
        kw.setdefault("t_cg", 0.9)
    if name in ("akpc", "packcache"):
        kw.setdefault("top_frac", 1.0)
    return get_policy(name, params=PARAMS, **kw)


def assert_same_costs(ref, got):
    a, b = ref.as_dict(), got.as_dict()
    for f in INT_FIELDS:
        assert a[f] == b[f], f"{f}: {a[f]} != {b[f]}"
    for f in FLOAT_FIELDS:
        assert np.isclose(a[f], b[f], rtol=1e-9, atol=1e-9), \
            f"{f}: {a[f]} != {b[f]}"


# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------
def test_dense_is_the_default():
    assert StateLayout.resolve(None) is DENSE
    assert DENSE.state_dims(60, 600) == (61, 600)
    assert DENSE.dump_row(60) == 60
    assert DENSE.is_dense_for(60, 600)
    assert DENSE.row_shards == 1


def test_bucketed_geometry_rounds_up():
    lay = StateLayout(kind="bucketed", row_bucket=64, col_bucket=32)
    assert lay.state_dims(50, 20) == (65, 32)
    assert lay.state_dims(64, 32) == (65, 32)
    assert lay.state_dims(65, 33) == (129, 64)
    assert lay.dump_row(50) == 64          # always the LAST row
    assert lay.state_dims(1, 1) == (65, 32)       # n=1 edge
    rows, cols = lay.state_dims(10_000, 600)
    assert rows == 10_048 + 1 and (rows - 1) % 64 == 0 and cols == 608
    assert not lay.is_dense_for(50, 20)
    assert lay.is_dense_for(64, 32)        # buckets land exactly on dims


def test_row_sharded_geometry_and_str_resolve():
    lay = StateLayout(kind="row_sharded", shards=4)
    assert lay.row_shards == 4
    assert lay.state_rows(60) % 4 == 0
    assert not lay.is_dense_for(60, 10)
    assert StateLayout(kind="row_sharded", shards=1).is_dense_for(60, 10)
    with pytest.raises(ValueError):
        StateLayout.resolve("row_sharded")      # needs a mesh or shards
    assert StateLayout.resolve("bucketed").kind == "bucketed"


def test_state_bytes_telemetry():
    assert DENSE.state_bytes(60, 600) == 61 * 600 * 8 + 61 * 4
    sh = StateLayout(kind="row_sharded", shards=4)
    assert sh.state_bytes_per_device(9999, 600) * 4 == sh.state_bytes(
        9999, 600)


def test_device_cgm_layout_gating():
    """The compact CGM carry is dense-n regardless of layout, so any
    row-unsharded layout qualifies (bucketed included); row-sharded
    state is refused — the in-scan segment reductions need every slot
    on one device."""
    from repro.core import cgm_jax
    from repro.core.engine import CacheState, CliquePartition

    st = CacheState.fresh(CliquePartition.singletons(8), 4)
    carry = cgm_jax.init_cgm_carry(st, None, None, n=8, m=4,
                                   uses_sizes=False, item_sizes=None,
                                   layout=BUCKETED, h=4, wcap=64)
    assert carry["of"].shape == (8,)                # dense-n carry
    with pytest.raises(ValueError):
        cgm_jax.init_cgm_carry(st, None, None, n=8, m=4,
                               uses_sizes=False, item_sizes=None,
                               layout=SHARDED3, h=4, wcap=64)


# ---------------------------------------------------------------------------
# replay parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layout", [None, BUCKETED, SHARDED3],
                         ids=["dense", "bucketed", "row_sharded"])
@pytest.mark.parametrize("policy", ["akpc", "no_packing", "ttl"])
def test_replay_parity_all_layouts(layout, policy):
    trace = _trace()
    ref = run_policy(_policy(policy), trace)
    got = run_policy_jax(_policy(policy), trace, layout=layout)
    assert_same_costs(ref.costs, got.costs)


def test_replay_parity_n_equals_1():
    # single-item catalog (the bundle generator needs n >= bundle size,
    # so build the trace by hand): one item pinging 3 servers
    from repro.traces.loader import Trace

    rng = np.random.default_rng(0)
    R = 400
    trace = Trace(
        times=np.sort(rng.uniform(0.0, 20.0, R)),
        servers=rng.integers(0, 3, R).astype(np.int32),
        items=np.zeros((R, 1), np.int32),
        n=1, m=3, name="one-item")
    ref = run_policy(_policy("no_packing"), trace)
    got = run_policy_jax(_policy("no_packing"), trace, layout=BUCKETED)
    assert_same_costs(ref.costs, got.costs)


@pytest.mark.parametrize("layout", [
    StateLayout(kind="bucketed"),           # default 1024-row buckets
    StateLayout(kind="row_sharded", shards=4),
], ids=["bucketed", "row_sharded"])
def test_replay_parity_large_catalog(layout):
    """The ISSUE-8 catalog-scale gate: n=10^4 items replays on the JAX
    backend with 1e-9 cost parity vs the numpy engine."""
    trace = _trace(n_items=10_000, n_servers=24, n_requests=4000, seed=1,
                   server_affinity=2)
    ref = run_policy(_policy("no_packing"), trace)
    got = run_policy_jax(_policy("no_packing"), trace, layout=layout)
    assert_same_costs(ref.costs, got.costs)


# ---------------------------------------------------------------------------
# bucket cohorts: compile per cohort, not per point
# ---------------------------------------------------------------------------
def test_mixed_shape_sweep_compiles_per_cohort():
    lay = StateLayout(kind="bucketed", row_bucket=64, col_bucket=16)
    shapes = [(30, 8), (40, 10), (90, 20), (100, 24)]
    pts = [SweepPoint("akpc", _trace(n_items=n, n_servers=m, seed=s),
                      dict(params=PARAMS, t_cg=0.9, top_frac=1.0),
                      tag=f"{n}x{m}")
           for s, (n, m) in enumerate(shapes)]
    cohorts = {lay.state_dims(n, m) for n, m in shapes}
    assert len(cohorts) == 2               # the grid must be ragged
    before = ej.SCAN_TRACES
    got = SweepEngine(backend="jax", layout=lay).run(pts)
    assert ej.SCAN_TRACES - before <= len(cohorts)
    for pt, g in zip(pts, got):
        ref = run_policy(get_policy(pt.policy, **pt.policy_kwargs),
                         pt.trace)
        assert_same_costs(ref.costs, g.costs)


def test_pad_schedule_preserves_state_geometry():
    trace = _trace()
    pol = _policy("akpc")
    pol.bind(trace.n, trace.m)
    from repro.core import CacheEnvironment, get_cost_model
    from repro.core.engine import CliquePartition

    env = CacheEnvironment.resolve(None, trace, PARAMS)
    s = ej.build_schedule(
        CliquePartition.singletons(trace.n), trace, pol.on_window,
        pol.t_cg, model=get_cost_model("table1", env), env=env,
        layout=BUCKETED)
    assert (s.state_rows, s.state_cols) == BUCKETED.state_dims(
        trace.n, trace.m)
    dims = {k: v + 7 for k, v in ej.schedule_dims(s).items()}
    padded = ej.pad_schedule(s, dims)
    assert (padded.state_rows, padded.state_cols) == (
        s.state_rows, s.state_cols)
    # padded event slots scatter into the dump row — the LAST state row
    K = s.state_rows - 1
    assert int(padded.xs["ev_c"].max()) <= K
    assert int(padded.xs["ev_c"][-1, -1]) == K


# ---------------------------------------------------------------------------
# snapshot round-trips
# ---------------------------------------------------------------------------
def _feed(sess, trace, lo, hi):
    sess.feed(trace.items[lo:hi], trace.servers[lo:hi],
              trace.times[lo:hi])


def test_snapshot_round_trip_dense_bucketed():
    trace = _trace()
    ref = CacheSession(_policy(), trace.n, trace.m)
    ref.feed_trace(trace)

    half = trace.n_requests // 2
    a = CacheSession(_policy(), trace.n, trace.m)          # dense
    _feed(a, trace, 0, half)
    b = CacheSession(_policy(), trace.n, trace.m, layout=BUCKETED)
    b.restore(a.snapshot())
    _feed(b, trace, half, trace.n_requests)
    assert_same_costs(ref.costs, b.costs)

    # and back: bucketed snapshot -> dense session
    c = CacheSession(_policy(), trace.n, trace.m, layout=BUCKETED)
    _feed(c, trace, 0, half)
    d = CacheSession(_policy(), trace.n, trace.m)
    d.restore(c.snapshot())
    _feed(d, trace, half, trace.n_requests)
    assert_same_costs(ref.costs, d.costs)


def test_snapshot_sharded_refuses_mismatched_shards():
    trace = _trace()
    a = CacheSession(_policy(), trace.n, trace.m,
                     layout=StateLayout(kind="row_sharded", shards=2))
    snap = a.snapshot()
    b = CacheSession(_policy(), trace.n, trace.m,
                     layout=StateLayout(kind="row_sharded", shards=4))
    with pytest.raises(ValueError, match="shard"):
        b.restore(snap)
    # dense and bucketed sessions accept the same snapshot freely
    CacheSession(_policy(), trace.n, trace.m).restore(snap)
    CacheSession(_policy(), trace.n, trace.m,
                 layout=BUCKETED).restore(snap)


# ---------------------------------------------------------------------------
# mesh placement (the CI multi-device lane)
# ---------------------------------------------------------------------------
needs_4_devices = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")


@needs_4_devices
def test_make_sweep_mesh_state_row_axis():
    from repro.core.state_layout import make_sweep_mesh

    mesh = make_sweep_mesh(state_rows=2)
    assert mesh.axis_names == ("scenario", "state_row")
    assert mesh.shape["state_row"] == 2
    with pytest.raises(ValueError):
        make_sweep_mesh(n_devices=4, state_rows=3)


@needs_4_devices
def test_row_sharded_state_spans_devices():
    import jax

    from repro.core.state_layout import make_sweep_mesh

    mesh = make_sweep_mesh(n_devices=4, state_rows=4)
    lay = StateLayout(kind="row_sharded", mesh=mesh)
    assert lay.row_shards == 4
    E0, a0 = ej.fresh_state_arrays(63, 10, lay)
    with jax.enable_x64(True):
        Ed, ad = lay.place_state(E0, a0)
    assert len(Ed.sharding.device_set) == 4
    assert len(ad.sharding.device_set) == 4


@needs_4_devices
def test_row_sharded_parity_on_mesh():
    """The acceptance gate: the row-sharded layout passes parity on a
    4-virtual-device CPU mesh (state rows spread over ``state_row``)."""
    from repro.core.state_layout import make_sweep_mesh

    mesh = make_sweep_mesh(n_devices=4, state_rows=4)
    lay = StateLayout(kind="row_sharded", mesh=mesh)
    trace = _trace()
    for policy in ("akpc", "no_packing"):
        ref = run_policy(_policy(policy), trace)
        got = run_policy_jax(_policy(policy), trace, layout=lay)
        assert_same_costs(ref.costs, got.costs)


@needs_4_devices
def test_sweep_engine_mesh_row_sharded():
    from repro.core.state_layout import make_sweep_mesh

    mesh = make_sweep_mesh(n_devices=4, state_rows=2)
    lay = StateLayout(kind="row_sharded", mesh=mesh)
    pts = [SweepPoint("akpc", _trace(seed=s),
                      dict(params=PARAMS, t_cg=0.9, top_frac=1.0))
           for s in range(2)]
    got = SweepEngine(backend="jax", mesh=mesh, layout=lay).run(pts)
    for pt, g in zip(pts, got):
        ref = run_policy(get_policy(pt.policy, **pt.policy_kwargs),
                         pt.trace)
        assert_same_costs(ref.costs, g.costs)
