"""JAX replay backend + vmapped SweepEngine (PR 5 tentpole).

Contracts under test:

* backend parity — ``run_policy(backend="jax")`` reproduces the NumPy
  engine cost-for-cost (1e-9 relative on float sums, integer counters
  exact) for EVERY registered policy, on table1 AND heterogeneous cost
  models, across the PR-2 chunking grid (batch size 1 / 7 / 4096 and a
  ragged mixed-backend session feed);
* sweep parity — ``SweepEngine`` results equal per-point serial
  ``run_policy`` at 1e-9 across all six registered policies and both
  cost models, including when points SHARE a host schedule (alpha sweeps)
  and when a group is replayed in one vmapped device call;
* session interop — a jax ``feed_trace`` syncs state/costs/window
  bookkeeping such that snapshots restore and numpy continuation agree
  with a pure-numpy session;
* backend guard rails — unknown backends and inexpressible cost models
  are refused loudly instead of silently falling back.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import (
    CacheEnvironment,
    CacheSession,
    CostParams,
    SweepEngine,
    SweepPoint,
    get_policy,
    list_policies,
    run_policy,
    sweep_points,
)
from repro.core import sweep as sweep_mod
from repro.core.cost import CostModel, register_cost_model
from repro.core.engine_jax import run_policy_jax
from repro.traces import SynthConfig, Trace, synth_trace

PARAMS = CostParams()
T_CG = 0.73            # never divides the batch grid: windows split batches
TOP_FRAC = 1.0
ALL_POLICIES = ("no_packing", "ttl", "learned", "packcache", "dp_greedy",
                "akpc", "akpc_no_acm", "akpc_base")

INT_FIELDS = ("n_requests", "n_item_requests", "n_misses", "n_hits",
              "items_transferred")
FLOAT_FIELDS = ("transfer", "caching", "keepalive_rent", "total")


def _trace(n_requests=4000, seed=3, m=12, size_dist="unit"):
    return synth_trace(SynthConfig(
        kind="netflix", n_items=60, n_servers=m, n_requests=n_requests,
        t_max=30.0, bundle_cover=1.0, bundle_zipf=0.7, seed=seed,
        size_dist=size_dist))


def _kwargs(name, **extra):
    kw = {"params": PARAMS}
    if name in ("packcache", "akpc", "akpc_no_acm", "akpc_base"):
        kw.update(t_cg=T_CG, top_frac=TOP_FRAC)
    if name in ("ttl", "learned"):     # keep-or-not policies: no packing knobs
        kw.update(t_cg=T_CG)
    if name == "dp_greedy":
        kw.update(top_frac=TOP_FRAC)
    kw.update(extra)
    return kw


def assert_same_costs(ref, got, rtol=1e-9):
    a = ref.as_dict() if not isinstance(ref, dict) else ref
    b = got.as_dict() if not isinstance(got, dict) else got
    for f in INT_FIELDS:
        assert a[f] == b[f], f"{f}: {a[f]} != {b[f]}"
    for f in FLOAT_FIELDS:
        assert np.isclose(a[f], b[f], rtol=rtol, atol=1e-9), \
            f"{f}: {a[f]} != {b[f]}"


@pytest.fixture(scope="module")
def trace():
    return _trace()


@pytest.fixture(scope="module")
def sized_trace():
    return _trace(size_dist="lognormal")


@pytest.fixture(scope="module")
def het_env(sized_trace):
    env = CacheEnvironment.skewed(
        sized_trace.n, sized_trace.m, PARAMS, price_sigma=0.8, seed=1)
    return CacheEnvironment.resolve(env, sized_trace, PARAMS)


# ---------------------------------------------------------------------------
# backend parity: every policy, both cost models
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ALL_POLICIES)
def test_jax_backend_matches_numpy_table1(trace, name):
    ref = run_policy(get_policy(name, **_kwargs(name)), trace)
    got = run_policy(get_policy(name, **_kwargs(name)), trace, backend="jax")
    assert got.policy == name
    assert got.n_windows == ref.n_windows
    assert np.array_equal(got.clique_sizes, ref.clique_sizes)
    assert_same_costs(ref.costs, got.costs)


@pytest.mark.parametrize("name", ALL_POLICIES)
def test_jax_backend_matches_numpy_heterogeneous(sized_trace, het_env, name):
    kw = _kwargs(name, env=het_env, cost_model="heterogeneous")
    ref = run_policy(get_policy(name, **kw), sized_trace)
    got = run_policy(get_policy(name, **kw), sized_trace, backend="jax")
    assert_same_costs(ref.costs, got.costs)


def test_jax_backend_matches_numpy_tiered(sized_trace):
    kw = _kwargs("akpc", cost_model="tiered")
    ref = run_policy(get_policy("akpc", **kw), sized_trace)
    got = run_policy(get_policy("akpc", **kw), sized_trace, backend="jax")
    assert_same_costs(ref.costs, got.costs)


# ---------------------------------------------------------------------------
# the PR-2 chunking grid: batch sizes 1 / 7 / 4096 + ragged mixed session
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bs", [1, 7, 4096])
@pytest.mark.parametrize("model", ["table1", "heterogeneous"])
def test_jax_backend_chunking_grid(trace, sized_trace, het_env, bs, model):
    tr = trace if model == "table1" else sized_trace
    kw = _kwargs("akpc")
    if model == "heterogeneous":
        kw.update(env=het_env, cost_model=model)
    ref = run_policy(get_policy("akpc", **kw), tr, batch_size=bs)
    got = run_policy_jax(get_policy("akpc", **kw), tr, batch_size=bs)
    assert_same_costs(ref.costs, got.costs)


def test_jax_session_ragged_mixed_chunking(trace):
    """numpy feed -> jax feed_trace -> numpy feed == offline numpy."""
    ref = run_policy(get_policy("akpc", **_kwargs("akpc")), trace)
    s = CacheSession(get_policy("akpc", **_kwargs("akpc")), trace.n, trace.m)
    c1, c2 = 501, 2503              # ragged cuts that split T_CG windows
    s.feed(trace.items[:c1], trace.servers[:c1], trace.times[:c1])
    s.feed_trace(trace.slice(c1, c2), backend="jax")
    s.feed(trace.items[c2:], trace.servers[c2:], trace.times[c2:])
    assert_same_costs(ref.costs, s.costs)


def test_jax_session_snapshot_roundtrip(trace):
    ref = run_policy(get_policy("akpc", **_kwargs("akpc")), trace)
    s = CacheSession(get_policy("akpc", **_kwargs("akpc")), trace.n, trace.m,
                     backend="jax")
    cut = 2503
    s.feed_trace(trace.slice(0, cut))
    snap = s.snapshot()
    s2 = CacheSession(get_policy("akpc", **_kwargs("akpc")),
                      trace.n, trace.m).restore(snap)
    s2.feed(trace.items[cut:], trace.servers[cut:], trace.times[cut:])
    assert_same_costs(ref.costs, s2.costs)


# ---------------------------------------------------------------------------
# SweepEngine parity
# ---------------------------------------------------------------------------
def test_sweep_matches_serial_all_policies_table1(trace):
    pts = [SweepPoint(name, trace, _kwargs(name)) for name in ALL_POLICIES]
    eng = SweepEngine()
    res = eng.run(pts)
    for pt, got in zip(pts, res):
        ref = run_policy(get_policy(pt.policy, **pt.policy_kwargs), trace)
        assert got.policy == pt.policy
        assert got.n_windows == ref.n_windows
        assert got.costs.model == "table1"
        assert_same_costs(ref.costs, got.costs)


def test_sweep_matches_serial_all_policies_heterogeneous(sized_trace, het_env):
    pts = [
        SweepPoint(name, sized_trace,
                   _kwargs(name, env=het_env, cost_model="heterogeneous"))
        for name in ALL_POLICIES
    ]
    res = SweepEngine().run(pts)
    for pt, got in zip(pts, res):
        ref = run_policy(
            get_policy(pt.policy, **pt.policy_kwargs), sized_trace)
        assert got.costs.model == "heterogeneous"
        assert_same_costs(ref.costs, got.costs)


def test_sweep_shares_schedules_across_alpha_axis(trace):
    """An alpha sweep runs clique generation ONCE and still matches the
    per-point serial replays (alpha never enters the CGM)."""
    alphas = [0.6, 0.8, 1.0]
    pts = [
        SweepPoint("akpc", trace,
                   dict(params=CostParams(alpha=a), t_cg=T_CG,
                        top_frac=TOP_FRAC))
        for a in alphas
    ]
    eng = SweepEngine()
    res = eng.run(pts)
    assert eng.last_n_schedules == 1        # one schedule, three scenarios
    totals = set()
    for pt, got in zip(pts, res):
        ref = run_policy(get_policy(pt.policy, **pt.policy_kwargs), trace)
        assert_same_costs(ref.costs, got.costs)
        totals.add(round(got.total, 6))
    assert len(totals) == len(alphas)       # scenarios really differ


def test_sweep_does_not_share_across_cgm_axes(trace):
    """theta changes the CGM -> separate schedules, results still match."""
    pts = [
        SweepPoint("packcache", trace,
                   dict(params=CostParams(theta=th), t_cg=T_CG,
                        top_frac=TOP_FRAC))
        for th in (0.1, 0.3)
    ]
    eng = SweepEngine()
    res = eng.run(pts)
    assert eng.last_n_schedules == 2
    for pt, got in zip(pts, res):
        ref = run_policy(get_policy(pt.policy, **pt.policy_kwargs), trace)
        assert_same_costs(ref.costs, got.costs)


def _copy_trace(tr, **changes):
    """A fresh ``Trace`` over copies of ``tr``'s arrays, with ``changes``."""
    fields = dict(times=tr.times.copy(), servers=tr.servers.copy(),
                  items=tr.items.copy(), n=tr.n, m=tr.m,
                  sizes=None if tr.sizes is None else tr.sizes.copy())
    fields.update(changes)
    return Trace(**fields)


def assert_same_run(ref, got):
    """Costs at 1e-9, counters, windows and partition exact."""
    assert_same_costs(ref.costs, got.costs)
    assert got.n_windows == ref.n_windows
    assert np.array_equal(got.clique_sizes, ref.clique_sizes)


def test_sweep_shares_schedules_across_equal_logs(trace):
    """Points that each hold their own ``Trace`` over equal logs share one
    schedule (sharing keys on content), and every lane still matches its
    serial replay."""
    pts = [
        SweepPoint("akpc", _copy_trace(trace),
                   dict(params=CostParams(alpha=a, rho=r), t_cg=T_CG,
                        top_frac=TOP_FRAC))
        for r in (0.5, 2.0) for a in (0.6, 0.8, 1.0)
    ]
    eng = SweepEngine()
    res = eng.run(pts)
    assert eng.last_n_schedules == 1
    for pt, got in zip(pts, res):
        ref = run_policy(get_policy(pt.policy, **pt.policy_kwargs), pt.trace)
        assert_same_run(ref, got)
    assert len({round(r.total, 6) for r in res}) == len(pts)


def _one_change(tr, field):
    """``tr`` with one request's time or server changed, or one item's
    size."""
    k = tr.n_requests // 2
    if field == "time":
        times = tr.times.copy()
        times[k] = 0.5 * (times[k - 1] + times[k])
        assert times[k] != tr.times[k]
        return _copy_trace(tr, times=times)
    if field == "server":
        servers = tr.servers.copy()
        servers[k] = (servers[k] + 1) % tr.m
        return _copy_trace(tr, servers=servers)
    sizes = tr.sizes.copy()
    sizes[int(tr.items[k, 0])] *= 2.0
    return _copy_trace(tr, sizes=sizes)


@pytest.mark.parametrize("field", ["time", "server", "sizes"])
def test_sweep_does_not_share_across_unequal_logs(trace, sized_trace, field):
    """Logs that differ in one time, one server or the item sizes get a
    schedule each (no false sharing), and each matches its serial
    replay."""
    base = sized_trace if field == "sizes" else trace
    kw = dict(params=PARAMS, t_cg=T_CG, top_frac=TOP_FRAC)
    if field == "sizes":
        kw.update(cost_model="heterogeneous")
    pts = [SweepPoint("akpc", tr, kw)
           for tr in (base, _one_change(base, field))]
    eng = SweepEngine()
    res = eng.run(pts)
    assert eng.last_n_schedules == 2
    for pt, got in zip(pts, res):
        ref = run_policy(get_policy(pt.policy, **pt.policy_kwargs), pt.trace)
        assert_same_run(ref, got)


def test_sweep_digests_each_array_set_once(trace, monkeypatch):
    """A call hashes each distinct set of trace arrays once, however many
    ``Trace`` objects wrap it."""
    calls = []
    real = sweep_mod.hashlib

    class Counting:
        @staticmethod
        def sha256():
            calls.append(1)
            return real.sha256()

    monkeypatch.setattr(sweep_mod, "hashlib", Counting)
    copy = _copy_trace(trace)
    pts = [
        SweepPoint("akpc", Trace(times=tr.times, servers=tr.servers,
                                 items=tr.items, n=tr.n, m=tr.m),
                   dict(params=CostParams(alpha=a), t_cg=T_CG,
                        top_frac=TOP_FRAC))
        for tr in (trace, copy) for a in (0.6, 0.8, 1.0)
    ]
    eng = SweepEngine()
    eng.run(pts)
    assert len(calls) == 2                  # two array sets, six traces
    assert eng.last_n_schedules == 1        # equal content: one schedule
    eng.run(pts)
    assert len(calls) == 4                  # the memo lasts one call


def test_sweep_numpy_backend_and_convenience(trace):
    grid = [dict(policy="no_packing", trace=trace,
                 policy_kwargs={"params": PARAMS})]
    a = sweep_points(grid, backend="numpy")[0]
    b = sweep_points(grid, backend="jax")[0]
    assert_same_costs(a.costs, b.costs)


def test_sweep_covers_registry():
    """The parity suites above must cover every registered policy (every
    registry name, aliases included, resolves to a covered policy)."""
    for name in list_policies():
        assert get_policy(name, params=PARAMS).name in ALL_POLICIES


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------
def test_unknown_backend_refused(trace):
    with pytest.raises(ValueError):
        run_policy(get_policy("no_packing", params=PARAMS), trace,
                   backend="tpu-magic")
    with pytest.raises(ValueError):
        SweepEngine(backend="tpu-magic")
    with pytest.raises(ValueError):
        CacheSession(get_policy("no_packing", params=PARAMS), trace.n,
                     trace.m, backend="tpu-magic")


def test_inexpressible_cost_model_refused(trace):
    """A custom registered CostModel has no jnp formula -> loud error."""

    class WeirdModel(CostModel):
        name = "weird_test_model"
        uses_sizes = False

        def dt(self):
            return np.full(self.env.m, self.params.dt)

        def transfer_cost_batch(self, counts, sizes, servers):
            return np.asarray(counts, float) ** 1.5

        def caching_rate(self, counts, sizes, servers):
            return np.asarray(counts, float)

    if "weird_test_model" not in __import__(
            "repro.core.cost", fromlist=["_COST_MODELS"])._COST_MODELS:
        register_cost_model("weird_test_model")(WeirdModel)
    pol = get_policy("no_packing", params=PARAMS,
                     cost_model="weird_test_model")
    with pytest.raises(NotImplementedError):
        run_policy(pol, trace, backend="jax")
    # the numpy backend still prices it fine
    run_policy(get_policy("no_packing", params=PARAMS,
                          cost_model="weird_test_model"), trace)


# ---------------------------------------------------------------------------
# trace-shard axis: shards/seeds as extra vmap lanes, costs merged
# ---------------------------------------------------------------------------
def test_sweep_shard_axis_matches_per_shard_serial():
    """A sharded point merges per-shard costs exactly and reports
    per-shard dispersion, lane-for-lane with the serial replays."""
    shards = [_trace(n_requests=1500, seed=s) for s in (3, 4, 5)]
    pts = [
        SweepPoint("akpc", shards,
                   dict(params=CostParams(alpha=a), t_cg=T_CG,
                        top_frac=TOP_FRAC))
        for a in (0.7, 0.9)
    ]
    eng = SweepEngine()
    res = eng.run(pts)
    # scenarios share the per-shard schedules: one build per shard
    assert eng.last_n_schedules == len(shards)
    for pt, got in zip(pts, res):
        subs = [run_policy(get_policy(pt.policy, **pt.policy_kwargs), tr)
                for tr in shards]
        merged = {f: sum(s.costs.as_dict()[f] for s in subs)
                  for f in INT_FIELDS + FLOAT_FIELDS}
        assert_same_costs(merged, got.costs)
        st = got.shard_stats
        assert st is not None and st["n"] == len(shards)
        np.testing.assert_allclose(
            st["totals"], [s.costs.total for s in subs], rtol=1e-9)
        np.testing.assert_allclose(
            st["mean"], np.mean(st["totals"]), rtol=1e-12)
        assert st["ci95"] >= 0.0


def test_sweep_shard_axis_numpy_backend_parity():
    """The numpy backend merges shards identically (same RunResult shape)."""
    shards = [_trace(n_requests=1200, seed=s) for s in (6, 7)]
    pt = SweepPoint("akpc", shards,
                    dict(params=PARAMS, t_cg=T_CG, top_frac=TOP_FRAC))
    got_j = SweepEngine(backend="jax").run([pt])[0]
    got_n = SweepEngine(backend="numpy").run([pt])[0]
    assert_same_costs(got_n.costs, got_j.costs)
    assert got_j.shard_stats["n"] == got_n.shard_stats["n"] == 2
    np.testing.assert_allclose(
        got_j.shard_stats["totals"], got_n.shard_stats["totals"], rtol=1e-9)
    # a plain (unsharded) point keeps shard_stats None
    plain = SweepEngine().run(
        [SweepPoint("akpc", shards[0],
                    dict(params=PARAMS, t_cg=T_CG, top_frac=TOP_FRAC))])[0]
    assert plain.shard_stats is None


def _stress_trace(profile, seed, n_requests=1200):
    return synth_trace(SynthConfig(
        kind="netflix", n_items=60, n_servers=12, n_requests=n_requests,
        t_max=30.0, bundle_cover=1.0, bundle_zipf=0.7, seed=seed,
        load_profile=profile,
        load_strength=4.0 if profile == "flash_crowd" else 0.8))


@pytest.mark.parametrize("profile", ["diurnal", "flash_crowd"])
def test_sweep_shard_axis_nonstationary_profiles(profile):
    """Non-stationary traces through the shard axis: merged totals equal
    the serial per-shard replays at 1e-9, and the shard-CI estimate
    tightens as seed-replica shards are added (1/sqrt(n) scaling holds to
    within the seed noise of these workloads)."""
    seeds = (3, 4, 5, 6, 7, 8)
    shards = [_stress_trace(profile, s) for s in seeds]
    kw = dict(params=PARAMS, t_cg=T_CG, top_frac=TOP_FRAC)
    got2, got6 = SweepEngine().run([
        SweepPoint("akpc", shards[:2], kw),
        SweepPoint("akpc", shards, kw),
    ])
    subs = [run_policy(get_policy("akpc", **kw), tr) for tr in shards]
    merged = {f: sum(s.costs.as_dict()[f] for s in subs)
              for f in INT_FIELDS + FLOAT_FIELDS}
    assert_same_costs(merged, got6.costs)
    np.testing.assert_allclose(
        got6.shard_stats["totals"], [s.costs.total for s in subs],
        rtol=1e-9)
    # non-stationarity really moved the per-shard costs apart
    assert got6.shard_stats["std"] > 0.0
    # CI width shrinks with the shard count (same seeds prefix both points)
    assert got6.shard_stats["ci95"] < got2.shard_stats["ci95"]


def test_sweep_shard_axis_rejects_mismatched_shards():
    a = _trace(n_requests=500, seed=1)
    b = synth_trace(SynthConfig(
        kind="netflix", n_items=61, n_servers=12, n_requests=500,
        t_max=30.0, bundle_cover=1.0, bundle_zipf=0.7, seed=2))
    with pytest.raises(ValueError, match="shards must share"):
        SweepEngine().run([SweepPoint(
            "akpc", [a, b], dict(params=PARAMS, t_cg=T_CG,
                                 top_frac=TOP_FRAC))])


def test_device_cgm_window_buffer_is_coarsely_bucketed():
    """The device-CGM window buffer is padded by at most a sixteenth, so
    the busiest windows of a 2,000-item deployment's logs (21,700-22,100
    rows with a 1,664-request step) share one compiled shape."""
    for x in range(1, 70_000, 7):
        c = sweep_mod._coarse(x)
        assert x <= c <= x + x / 16
    assert {sweep_mod._coarse(x) for x in range(21_505, 22_529)} == {22_528}


# ---------------------------------------------------------------------------
# staging: every lane's fresh state is made on the device
# ---------------------------------------------------------------------------
def _staging_points(path, n_alpha):
    """``n_alpha`` points on the alpha axis per schedule, shaped to take
    one staging path of ``SweepEngine``; returns (points, lanes)."""
    alphas = np.linspace(0.6, 1.0, n_alpha).tolist()
    kw = dict(t_cg=T_CG, top_frac=TOP_FRAC)
    tr = _trace(n_requests=1200)
    if path == "single":                # one schedule, S spec lanes
        return [SweepPoint("akpc", tr, dict(
            params=CostParams(alpha=a), **kw)) for a in alphas], n_alpha
    if path == "cohort":                # two schedules stacked as lanes
        logs = (tr, _trace(n_requests=1200, seed=4))
        return [SweepPoint("akpc", lg, dict(
            params=CostParams(alpha=a), **kw))
            for lg in logs for a in alphas], 2 * n_alpha
    if path == "shards":                # scenarios x trace shards
        shards = [tr, _trace(n_requests=1200, seed=4)]
        return [SweepPoint("akpc", shards, dict(
            params=CostParams(alpha=a), **kw)) for a in alphas], 2 * n_alpha
    return [SweepPoint("akpc", tr, dict(      # device-CGM lanes
        params=CostParams(alpha=a, theta=th), **kw))
        for th in (0.1, 0.3) for a in alphas], 2 * n_alpha


def _lane_spec_nbytes(eng, pt, cgm):
    """Host bytes of one lane's cost spec, and of its clique-generation
    spec where it is a device-CGM lane."""
    from repro.core import cgm_jax

    pr = eng._prepare([pt])[0][0]
    specs = list(pr["spec"].values())
    if cgm:
        cfg = pr["policy"].config
        specs += cgm_jax.cgm_spec(cfg, cfg.params, pt.trace.n).values()
    return sum(np.asarray(v).nbytes for v in specs)


@pytest.mark.parametrize("path", ["single", "cohort", "shards", "cgm"])
def test_sweep_stages_lane_state_on_device(path, monkeypatch, tmp_path):
    """Each staging path makes the lanes' fresh state on the device: the
    bytes a call copies from the host grow with the lanes by the per-lane
    spec alone, ``stats["staged_bytes"]`` equals the ``sweep.stage``
    spans' ``bytes``, a repeated call compiles no scan, and every point
    equals its serial replay."""
    from repro import obs
    from repro.core import cgm_jax
    from repro.core import engine_jax as ej

    monkeypatch.setattr(sweep_mod, "_COHORT_DIMS", {})
    entry = {"single": "run_schedule", "cohort": "run_schedules",
             "shards": "run_schedules", "cgm": "run_cgm_schedule"}[path]
    mod = cgm_jax if path == "cgm" else ej
    real, seen = getattr(mod, entry), []

    def spy(*a, **k):
        state = a[4] if path == "cgm" else a[3:5]
        seen.append(all(isinstance(v, jax.Array) for v in (
            state.values() if path == "cgm" else state)))
        return real(*a, **k)

    monkeypatch.setattr(mod, entry, spy)
    eng = SweepEngine()
    pts, lanes = _staging_points(path, 2)
    eng.run(pts)                                    # compiles
    traces = ej.SCAN_TRACES + cgm_jax.SCAN_TRACES
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        res = eng.run(pts)
    finally:
        jax.profiler.stop_trace()
    assert ej.SCAN_TRACES + cgm_jax.SCAN_TRACES == traces
    staged = eng.stats["staged_bytes"]
    stage = [st for name, _, _, st in obs.read(str(tmp_path))
             if name == obs.PREFIX + "sweep.stage"]
    assert staged == sum(st["bytes"] for st in stage) > 0
    assert seen and all(seen)                       # device state only
    assert (eng.stats["cgm_lanes"] > 0) == (path == "cgm")

    pts2, lanes2 = _staging_points(path, 4)
    eng.run(pts2)
    per_lane = _lane_spec_nbytes(eng, pts[0], path == "cgm")
    assert eng.stats["staged_bytes"] - staged == (lanes2 - lanes) * per_lane

    for pt, got in zip(pts, res):
        if path == "shards":
            subs = [run_policy(get_policy(pt.policy, **pt.policy_kwargs),
                               tr) for tr in pt.trace]
            merged = {f: sum(s.costs.as_dict()[f] for s in subs)
                      for f in INT_FIELDS + FLOAT_FIELDS}
            assert_same_costs(merged, got.costs)
            continue
        ref = run_policy(get_policy(pt.policy, **pt.policy_kwargs),
                         pt.trace)
        assert_same_run(ref, got)


def test_sweep_one_device_mesh_matches_no_mesh(trace, monkeypatch):
    """Through ``SweepEngine(mesh=...)`` on a one-device sweep mesh the
    lanes' state is still made on the device, and every point equals the
    engine without a mesh, counters and costs."""
    from repro.core import engine_jax as ej
    from repro.core.state_layout import make_sweep_mesh

    real, seen = ej.run_schedule, []

    def spy(*a, **k):
        seen.append(all(isinstance(v, jax.Array) for v in a[3:5]))
        return real(*a, **k)

    monkeypatch.setattr(ej, "run_schedule", spy)
    pts = [SweepPoint("akpc", trace, dict(
        params=CostParams(alpha=a, rho=r), t_cg=T_CG, top_frac=TOP_FRAC))
        for r in (0.5, 2.0) for a in (0.6, 1.0)]
    got = SweepEngine(mesh=make_sweep_mesh(n_devices=1)).run(pts)
    want = SweepEngine().run(pts)
    assert seen == [True, True]
    for w, g in zip(want, got):
        assert w.costs.as_dict() == g.costs.as_dict()
        assert np.array_equal(w.clique_sizes, g.clique_sizes)


@pytest.mark.parametrize("uses_sizes", [False, True])
def test_fresh_cgm_lanes_equal_stacked_host_carry(sized_trace, uses_sizes):
    """The device-made device-CGM lanes hold, field for field and bit for
    bit, S copies of the carry ``init_cgm_carry`` builds on the host for
    an empty cache over singleton cliques; the host copies one lane's
    per-item fields only."""
    from repro.core import cgm_jax
    from repro.core.cliques import CliquePartition
    from repro.core.engine import CacheState

    tr = sized_trace
    sched = cgm_jax.build_cgm_schedule(tr, T_CG, uses_sizes=uses_sizes,
                                       hot_dims=[(TOP_FRAC, False)])
    sizes = tr.sizes if uses_sizes else None
    one = cgm_jax.init_cgm_carry(
        CacheState.fresh(CliquePartition.singletons(tr.n), tr.m), None, None,
        n=tr.n, m=tr.m, uses_sizes=uses_sizes, item_sizes=sizes,
        schedule=sched)
    lanes, nbytes = cgm_jax.fresh_cgm_lanes(
        3, n=tr.n, m=tr.m, uses_sizes=uses_sizes, item_sizes=sizes,
        schedule=sched)
    assert sorted(lanes) == sorted(one)
    for k, v in one.items():
        got = np.asarray(lanes[k])
        assert got.dtype == v.dtype, k
        assert np.array_equal(got, np.stack([v] * 3)), k
    assert nbytes == sum(one[k].nbytes for k in ("of", "cnt", "vol")
                         if k in one)
