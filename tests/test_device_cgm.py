"""Device-resident clique generation (PR 6 tentpole; DESIGN.md §11).

Contracts under test:

* oracle parity — the on-device CGM (window CRM -> adjust -> split ->
  approximate merge, inside the jit'd scan) produces partitions
  element-for-element identical to the frozen ``cliques_ref`` oracle at
  EVERY chained T_CG boundary, across a fig7-style theta x gamma x omega
  grid run as ONE vmapped device call;
* zero host CGM calls — a device replay / fig7 sweep never calls the
  host ``generate_cliques`` (the ``cliques.CGM_CALLS`` counter stays
  flat) and a CGM-axis sweep shares ONE schedule;
* gating — ``wants_device_cgm`` refuses non-AKPC policies, custom CRM
  hooks and oversized catalogs; ``REPRO_JAX_CGM=off`` forces the host
  path and still reproduces the numpy engine;
* kernels — the ``merge_step.merge_density`` Pallas kernel is
  bit-identical to the jnp fallback in interpret mode.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import (
    CacheEnvironment,
    CostParams,
    SweepEngine,
    SweepPoint,
    get_policy,
    run_policy,
)
from repro.core import cliques as cliques_mod
from repro.core import cliques_ref as oracle
from repro.core import cgm_jax
from repro.core import sweep as sweep_mod
from repro.core.crm import build_window_crm
from repro.core.engine_jax import JaxReplayEngine, run_policy_jax
from repro.traces import SynthConfig, Trace, synth_trace

N_ITEMS = 48
T_CG = 0.73
TOP_FRAC = 0.5

THETAS = (0.1, 0.3)
GAMMAS = (0.6, 0.95)
OMEGAS = (3, 5)


def _trace(n_requests=900, seed=5, m=6):
    return synth_trace(SynthConfig(
        kind="netflix", n_items=N_ITEMS, n_servers=m,
        n_requests=n_requests, t_max=9.0, bundle_cover=1.0,
        bundle_zipf=0.7, seed=seed))


def _kw(theta, gamma, omega, **extra):
    kw = dict(params=CostParams(theta=theta, gamma=gamma, omega=omega),
              t_cg=T_CG, top_frac=TOP_FRAC)
    kw.update(extra)
    return kw


def _oracle_trajectory(trace, theta, gamma, omega, *, enable_split=True,
                       enable_acm=True, t_cg=T_CG, top_frac=TOP_FRAC):
    """The frozen-oracle partition at every T_CG boundary, walking the
    trace exactly as ``ReplayEngine.replay`` / ``build_cgm_schedule`` do."""
    times = trace.times
    R = times.shape[0]
    next_cg = float(times[0]) + t_cg
    win_start = pos = 0
    prev = prev_crm = None
    parts = []
    while pos < R:
        cut = int(np.searchsorted(times, next_cg, side="left"))
        if cut <= pos:
            t = float(times[pos])
            crm = build_window_crm(
                trace.items[win_start:pos], trace.n, theta,
                top_frac=top_frac)
            prev = oracle.generate_cliques(
                prev, prev_crm, crm, trace.n, omega, gamma,
                enable_split=enable_split, enable_approx_merge=enable_acm)
            parts.append(prev.clique_of.copy())
            prev_crm = crm
            win_start = pos
            while next_cg <= t:
                next_cg += t_cg
            continue
        pos = cut
    return parts


@pytest.fixture(scope="module")
def trace():
    return _trace()


def test_device_partitions_match_oracle_fig7_grid(trace):
    """One vmapped device call over the theta x gamma x omega grid; every
    lane's partition at every chained boundary == the cliques_ref oracle,
    element for element."""
    combos = [(th, g, om) for th in THETAS for g in GAMMAS for om in OMEGAS]
    pol0 = get_policy("akpc", **_kw(*combos[0]))
    pol0.bind(trace.n, trace.m)
    env = CacheEnvironment.resolve(None, trace, pol0.params)
    jeng = JaxReplayEngine(trace.n, trace.m, pol0.params, env=env)
    sched = cgm_jax.build_cgm_schedule(trace, T_CG, uses_sizes=False)
    assert sched.boundary_steps.size >= 3          # chained windows
    cspecs = []
    for th, g, om in combos:
        p = get_policy("akpc", **_kw(th, g, om))
        p.bind(trace.n, trace.m)
        cspecs.append(cgm_jax.cgm_spec(p.config, p.config.params, trace.n))
    cspec = {k: np.stack([np.asarray(c[k]) for c in cspecs])
             for k in cspecs[0]}
    S = len(combos)
    carry1 = cgm_jax.init_cgm_carry(
        jeng.engine.state, None, None, n=trace.n, m=trace.m,
        uses_sizes=False, item_sizes=None, schedule=sched)
    carry0 = {k: np.stack([v] * S) for k, v in carry1.items()}
    spec = {k: np.stack([v] * S) for k, v in jeng._spec.items()}
    final, ofs, _ = cgm_jax.run_cgm_schedule(
        sched, spec, jeng._statics, cspec, carry0, None)
    for lane, (th, g, om) in enumerate(combos):
        want = _oracle_trajectory(trace, th, g, om)
        assert len(want) == sched.boundary_steps.size
        for w, (b, ref_of) in enumerate(zip(sched.boundary_steps, want)):
            got = ofs[lane, int(b)]
            assert np.array_equal(got, ref_of), \
                f"theta={th} gamma={g} omega={om} window={w}"
        assert np.array_equal(final["of"][lane], want[-1])


@pytest.mark.parametrize("name", ["akpc", "akpc_no_acm", "akpc_base"])
def test_device_ablation_variants_match_oracle(trace, name):
    """Split/merge ablations flow through the same static gates."""
    pol = get_policy(name, **_kw(0.2, 0.85, 4))
    cfg = pol.config
    res = run_policy_jax(pol, trace)
    want = _oracle_trajectory(
        trace, 0.2, 0.85, 4 if cfg.enable_split else trace.n,
        enable_split=cfg.enable_split,
        enable_acm=cfg.enable_approx_merge)
    # run_policy_jax syncs the policy's final partition from the device
    assert np.array_equal(
        res.clique_sizes, np.bincount(want[-1]).astype(np.int64))


def test_fig7_sweep_zero_host_cgm_calls(trace):
    """The acceptance bar: a fig7 sweep shares ONE schedule and performs
    ZERO host clique-generation calls — and still matches the numpy
    engine cost-for-cost."""
    pts = [SweepPoint("akpc", trace, _kw(th, g, om))
           for th in THETAS for g in GAMMAS for om in OMEGAS]
    eng = SweepEngine()
    before = cliques_mod.CGM_CALLS
    res = eng.run(pts)
    assert cliques_mod.CGM_CALLS == before          # zero host CGM calls
    assert eng.last_n_schedules == 1                # one shared schedule
    for pt, got in zip(pts[:2], res[:2]):           # spot-check cost parity
        ref = run_policy(get_policy(pt.policy, **pt.policy_kwargs), trace)
        assert got.n_windows == ref.n_windows
        assert np.array_equal(got.clique_sizes, ref.clique_sizes)
        for f in ("transfer", "caching", "keepalive_rent", "total"):
            assert np.isclose(ref.costs.as_dict()[f], got.costs.as_dict()[f],
                              rtol=1e-9, atol=1e-9), f


def test_fig7_sweep_own_traces_form_one_device_group(trace):
    """A theta/gamma grid whose points each hold their own ``Trace`` over
    one log still forms one device-CGM group with one schedule."""
    pts = [SweepPoint("akpc", Trace(times=trace.times, servers=trace.servers,
                                    items=trace.items, n=trace.n,
                                    m=trace.m), _kw(th, g, 4))
           for th in THETAS for g in GAMMAS]
    eng = SweepEngine()
    before = cliques_mod.CGM_CALLS
    res = eng.run(pts)
    assert cliques_mod.CGM_CALLS == before          # zero host CGM calls
    assert eng.last_n_schedules == 1
    for pt, got in zip(pts[:2], res[:2]):
        ref = run_policy(get_policy(pt.policy, **pt.policy_kwargs), trace)
        assert np.array_equal(got.clique_sizes, ref.clique_sizes)
        assert np.isclose(ref.costs.total, got.costs.total,
                          rtol=1e-9, atol=1e-9)


def test_replay_routes_device_and_counter_flat(trace):
    before = cliques_mod.CGM_CALLS
    got = run_policy_jax(get_policy("akpc", **_kw(0.2, 0.85, 4)), trace)
    assert cliques_mod.CGM_CALLS == before
    ref = run_policy(get_policy("akpc", **_kw(0.2, 0.85, 4)), trace)
    assert np.array_equal(got.clique_sizes, ref.clique_sizes)
    assert got.costs.n_misses == ref.costs.n_misses


def test_escape_hatch_forces_host_path(trace, monkeypatch):
    monkeypatch.setenv("REPRO_JAX_CGM", "off")
    pol = get_policy("akpc", **_kw(0.2, 0.85, 4))
    pol.bind(trace.n, trace.m)
    env = CacheEnvironment.resolve(None, trace, pol.params)
    from repro.core.cost import get_cost_model

    model = get_cost_model("table1", env)
    assert not cgm_jax.wants_device_cgm(pol, trace, model)
    before = cliques_mod.CGM_CALLS
    got = run_policy_jax(get_policy("akpc", **_kw(0.2, 0.85, 4)), trace)
    assert cliques_mod.CGM_CALLS > before           # host CGM ran
    ref = run_policy(get_policy("akpc", **_kw(0.2, 0.85, 4)), trace)
    assert np.isclose(got.costs.total, ref.costs.total, rtol=1e-9)


def test_wants_device_cgm_gating(trace, monkeypatch):
    pol = get_policy("akpc", **_kw(0.2, 0.85, 4))
    pol.bind(trace.n, trace.m)
    env = CacheEnvironment.resolve(None, trace, pol.params)
    from repro.core.cost import get_cost_model

    model = get_cost_model("table1", env)
    assert cgm_jax.wants_device_cgm(pol, trace, model)
    # non-AKPC configs are refused (packcache has its own window logic)
    pc = get_policy("packcache", params=CostParams(), t_cg=T_CG,
                    top_frac=TOP_FRAC)
    pc.bind(trace.n, trace.m)
    assert not cgm_jax.wants_device_cgm(pc, trace, model)
    # custom CRM hooks mean the host hooks must run
    hooked = get_policy("akpc", **_kw(0.2, 0.85, 4,
                                      crm_matmul=lambda H: H.T @ H))
    hooked.bind(trace.n, trace.m)
    assert not cgm_jax.wants_device_cgm(hooked, trace, model)
    # the catalog size no longer gates the path — only the padded hot
    # capacity does; big-catalog traces are admitted as long as their
    # window working set keeps h under MAX_DEVICE_CGM_HOT
    big = synth_trace(SynthConfig(
        kind="netflix", n_items=4 * 256 + 8, n_servers=4,
        n_requests=40, t_max=2.0, seed=0))
    big_env = CacheEnvironment.resolve(None, big, pol.params)
    big_model = get_cost_model("table1", big_env)
    assert cgm_jax.wants_device_cgm(pol, big, big_model)
    # ... but an oversized hot capacity falls back in auto mode
    monkeypatch.setattr(cgm_jax, "MAX_DEVICE_CGM_HOT", 8)
    assert not cgm_jax.wants_device_cgm(pol, big, big_model)
    monkeypatch.setenv("REPRO_JAX_CGM", "force")
    assert cgm_jax.wants_device_cgm(pol, big, big_model)
    monkeypatch.delenv("REPRO_JAX_CGM")
    monkeypatch.undo()
    # non-prune approximate-merge lanes need the (2n, 2n) merge space:
    # admitted while n stays within the bound the pruned (2h, 2h) space
    # has, refused above it
    soft = get_policy("akpc", **_kw(0.2, 0.4, 4))
    soft.bind(big.n, big.m)
    assert big.n <= cgm_jax.MAX_DEVICE_CGM_HOT
    assert cgm_jax.wants_device_cgm(soft, big, big_model)
    monkeypatch.setattr(cgm_jax, "MAX_DEVICE_CGM_HOT", big.n - 1)
    assert not cgm_jax.wants_device_cgm(soft, big, big_model)
    assert cgm_jax.wants_device_cgm(pol, big, big_model)  # pruning lane
    monkeypatch.undo()
    soft.bind(trace.n, trace.m)
    assert cgm_jax.wants_device_cgm(soft, trace, model)


def test_merge_density_kernel_matches_jnp_interpret():
    import jax
    import jax.numpy as jnp

    from repro.core.cliques import _densities
    from repro.kernels.merge_step import (
        merge_density, merge_density_jnp, merge_edge_floor,
    )

    rng = np.random.default_rng(0)
    with jax.enable_x64(True):
        for S, omega, gamma in [(16, 4, 0.5), (120, 6, 0.8), (257, 3, 0.34)]:
            B = rng.integers(0, 4, (S, S)).astype(np.float32)
            X = B + B.T
            np.fill_diagonal(X, rng.integers(0, 20, S) * 2)
            sizes = rng.integers(0, omega, S).astype(np.int32)
            Xj, sj = jnp.asarray(X), jnp.asarray(sizes)
            om = jnp.asarray(omega, jnp.int32)
            ef = jnp.asarray(merge_edge_floor(omega, gamma))
            D_k = np.asarray(merge_density(Xj, sj, om, ef, interpret=True))
            D_j = np.asarray(merge_density_jnp(Xj, sj, om, ef))
            # the host's thresholded densities, carried as edge counts
            within = np.diag(X) / 2.0
            e_u = (within[:, None] + within[None, :]) + X
            dens = _densities(X, sizes, omega)
            D_r = np.where(dens >= gamma, e_u, -1.0).astype(np.float32)
            assert np.array_equal(D_k, D_r), (S, omega, gamma)
            assert np.array_equal(D_j, D_r), (S, omega, gamma)


@pytest.mark.parametrize("omega", [2, 3, 4, 5, 6, 8, 13, 40])
def test_merge_edge_floor_is_the_host_density_bar(omega):
    """e >= merge_edge_floor(omega, gamma) iff the host's f32 density
    e / e_max passes gamma, for every count up to e_max."""
    from repro.kernels.merge_step import merge_edge_floor

    em = np.float32(omega * (omega - 1) / 2.0)
    e = np.arange(int(em) + 1, dtype=np.float32)
    gammas = [0.0, 1e-9, 0.1, 1 / 3, 0.5, 0.6, 0.7, 2 / 3, 0.8, 0.9,
              (omega - 2) / omega, (omega - 1) / omega, 1.0, 1.2]
    gammas += [float(k) / float(em) for k in range(int(em) + 1)]
    for g in gammas:
        host = (e / em) >= np.float32(g)
        assert np.array_equal(e >= merge_edge_floor(omega, g), host), g


def test_device_cgm_with_kernels_interpret(trace):
    """The in-trace Pallas path (crm_update + clique_pair_edges +
    merge_density, interpret mode on CPU) is cost- and partition-identical
    to the host."""
    pol = get_policy("akpc", **_kw(0.2, 0.85, 4))
    pol.bind(trace.n, trace.m)
    env = CacheEnvironment.resolve(None, trace, pol.params)
    jeng = JaxReplayEngine(trace.n, trace.m, pol.params, env=env)
    sched = cgm_jax.build_cgm_schedule(trace, T_CG, uses_sizes=False)
    cspec = cgm_jax.cgm_spec(pol.config, pol.config.params, trace.n)
    carry0 = cgm_jax.init_cgm_carry(
        jeng.engine.state, None, None, n=trace.n, m=trace.m,
        uses_sizes=False, item_sizes=None, schedule=sched)
    final, _, _ = cgm_jax.run_cgm_schedule(
        sched, jeng._spec, jeng._statics, cspec, carry0, None,
        use_kernels=True)
    ref = run_policy(get_policy("akpc", **_kw(0.2, 0.85, 4)), trace)
    part = cgm_jax.partition_from_of(trace.n, final["of"])
    assert np.array_equal(part.sizes(), ref.clique_sizes)
    acc = final["acc"]
    d = ref.costs.as_dict()
    assert np.isclose(acc[0], d["transfer"], rtol=1e-9)
    assert np.isclose(acc[1], d["caching"], rtol=1e-9)
    assert int(acc[3]) == d["n_misses"]


# ---------------------------------------------------------------------------
# compact hot space beyond the old 256-item cap (DESIGN.md §15)
# ---------------------------------------------------------------------------
N_BIG = 4096
T_CG_BIG = 2.0


@pytest.fixture(scope="module")
def big_trace():
    return synth_trace(SynthConfig(
        kind="spotify", n_items=N_BIG, n_servers=12, n_requests=1500,
        t_max=8.0, bundle_cover=1.0, bundle_zipf=0.7, seed=3))


def test_big_catalog_chained_parity_vs_oracle(big_trace):
    """n=4096 — far beyond the old MAX_DEVICE_CGM_N = 256 cap: the
    compact hot-space boundary reproduces the cliques_ref oracle
    element-for-element at every chained window."""
    trace = big_trace
    pol = get_policy("akpc", params=CostParams(theta=0.2, gamma=0.85,
                                               omega=4),
                     t_cg=T_CG_BIG, top_frac=TOP_FRAC)
    pol.bind(trace.n, trace.m)
    env = CacheEnvironment.resolve(None, trace, pol.params)
    jeng = JaxReplayEngine(trace.n, trace.m, pol.params, env=env)
    sched = cgm_jax.build_cgm_schedule(
        trace, T_CG_BIG, uses_sizes=False,
        hot_dims=cgm_jax.policy_hot_dims(pol))
    assert sched.boundary_steps.size >= 3          # chained windows
    assert sched.h < trace.n                       # genuinely compact
    cspec = cgm_jax.cgm_spec(pol.config, pol.config.params, trace.n)
    carry0 = cgm_jax.init_cgm_carry(
        jeng.engine.state, None, None, n=trace.n, m=trace.m,
        uses_sizes=False, item_sizes=None, schedule=sched)
    final, ofs, _ = cgm_jax.run_cgm_schedule(
        sched, jeng._spec, jeng._statics, cspec, carry0, None)
    want = _oracle_trajectory(trace, 0.2, 0.85, 4, t_cg=T_CG_BIG)
    assert len(want) == sched.boundary_steps.size
    for w, (b, ref_of) in enumerate(zip(sched.boundary_steps, want)):
        assert np.array_equal(ofs[int(b)], ref_of), f"window={w}"
    assert np.array_equal(final["of"], want[-1])


@pytest.mark.parametrize("layout_kind", ["dense", "bucketed"])
def test_big_catalog_layouts_route_device(big_trace, layout_kind):
    """run_policy_jax keeps the CGM on device at n=4096 under both the
    dense and the bucketed StateLayout, and the final partition still
    matches the frozen oracle."""
    from repro.core.state_layout import StateLayout

    layout = None if layout_kind == "dense" else StateLayout(
        kind="bucketed")
    trace = big_trace
    pol = get_policy("akpc", params=CostParams(theta=0.2, gamma=0.85,
                                               omega=4),
                     t_cg=T_CG_BIG, top_frac=TOP_FRAC)
    before = cliques_mod.CGM_CALLS
    got = run_policy_jax(pol, trace, layout=layout)
    assert cliques_mod.CGM_CALLS == before          # zero host CGM calls
    want = _oracle_trajectory(trace, 0.2, 0.85, 4, t_cg=T_CG_BIG)
    assert np.array_equal(
        got.clique_sizes, np.bincount(want[-1]).astype(np.int64))


def test_wants_device_cgm_accepts_ten_k_catalog():
    """The ISSUE-10 acceptance bar: auto-routing admits 10^4 items."""
    from repro.core.cost import get_cost_model

    big = synth_trace(SynthConfig(
        kind="netflix", n_items=10_000, n_servers=8, n_requests=60,
        t_max=2.0, seed=0))
    pol = get_policy("akpc", **_kw(0.2, 0.85, 4))
    pol.bind(big.n, big.m)
    env = CacheEnvironment.resolve(None, big, pol.params)
    model = get_cost_model("table1", env)
    assert cgm_jax.wants_device_cgm(pol, big, model)


def test_window_crm_f32_exact_guard():
    """Co-occurrence counts live in f32: a window capacity at 2**24
    must be refused BEFORE any tracing (counts could silently lose
    integer exactness), while wcap just below the bound traces fine —
    checked abstractly so no (2**24, d) buffer is ever allocated."""
    import jax.numpy as jnp

    with pytest.raises(ValueError, match="f32"):
        cgm_jax._window_crm_device(
            None, None, n=8, h=4, wcap=cgm_jax._F32_EXACT,
            use_kernels=False)

    n, h, dbuf = 8, 4, 2
    wcap = cgm_jax._F32_EXACT - 1
    carry = {
        "wcnt": jax.ShapeDtypeStruct((n + 1,), jnp.int32),
        "wbuf": jax.ShapeDtypeStruct((wcap, dbuf), jnp.int32),
        "wlen": jax.ShapeDtypeStruct((), jnp.int32),
    }
    cspec = {
        "theta": jax.ShapeDtypeStruct((), jnp.float32),
        "top_frac": jax.ShapeDtypeStruct((), jnp.float64),
        "of_catalog": jax.ShapeDtypeStruct((), jnp.bool_),
    }
    out = jax.eval_shape(
        lambda c, s: cgm_jax._window_crm_device(
            c, s, n=n, h=h, wcap=wcap, use_kernels=False),
        carry, cspec)
    assert out[3].shape == (h, h)                  # raw CRM
    assert out[5].shape == (h, h)                  # binary CRM


# ---------------------------------------------------------------------------
# merge lanes outside the pruning regime beside pruning lanes (DESIGN §15)
# ---------------------------------------------------------------------------
#: (theta, gamma, omega): two lanes outside the merge-pruning regime
#: (gamma at the (omega - 2) / omega bar, and omega = 2) and two inside it
MIXED_GRID = [(0.2, 0.6, 5), (0.2, 0.85, 2), (0.2, 0.85, 5), (0.1, 0.9, 4)]


@pytest.mark.parametrize("top_frac, near_n", [(1.0, True), (0.25, False)])
def test_sweep_runs_non_pruning_lanes_on_device(monkeypatch, top_frac,
                                                near_n):
    """Non-pruning merge lanes run as device-CGM lanes of one sweep call
    beside pruning ones at n = 320: partitions equal the oracle at every
    boundary, counters and costs equal the numpy engine, and no point
    walks the host CGM.  Where the hot capacity h is within one bucket
    of n, all lanes share one program at scap = 2n; where h is well
    below n, the pruning lanes keep scap = 2h and the others run as a
    group of their own at 2n."""
    t_cg = 1.5
    trace = synth_trace(SynthConfig(
        kind="netflix", n_items=320, n_servers=6, n_requests=2400,
        t_max=6.0, bundle_cover=1.0, bundle_zipf=0.7, seed=11))
    n = trace.n

    def kw(th, g, om):
        return dict(params=CostParams(theta=th, gamma=g, omega=om),
                    t_cg=t_cg, top_frac=top_frac)

    runs = []
    run = cgm_jax.run_cgm_schedule

    def spy(schedule, spec, statics, cspec, *a, **k):
        out = run(schedule, spec, statics, cspec, *a, **k)
        runs.append((schedule, cspec, out))
        return out

    monkeypatch.setattr(cgm_jax, "run_cgm_schedule", spy)
    # no padded dims carried over from another sweep of this catalog
    monkeypatch.setattr(sweep_mod, "_COHORT_DIMS", {})
    eng = SweepEngine()
    before = cliques_mod.CGM_CALLS
    res = eng.run([SweepPoint("akpc", trace, kw(*c)) for c in MIXED_GRID])
    assert cliques_mod.CGM_CALLS == before          # zero host CGM calls
    st = eng.stats
    assert st["cgm_lanes"] == len(MIXED_GRID) and st["cgm_host_points"] == 0
    sched = runs[0][0]
    nbd = int(sched.boundary_steps.size)
    assert nbd >= 2                                 # chained windows
    assert st["cgm_boundaries"] == nbd * len(MIXED_GRID)
    assert (st["cgm_hot"], st["cgm_window_rows"]) == (sched.h, sched.wcap)
    assert st["cgm_merge_space"] == 2 * n
    assert (n - sched.h <= cgm_jax.HOT_BUCKET) == near_n
    # one program per merge space, each at the space its lanes need
    lanes_of = []
    for schedule, cspec, _ in runs:
        assert schedule is sched                   # one shared schedule
        prunes = cgm_jax.lane_prunes(cspec)
        _, full = cgm_jax.cgm_loop_statics(
            cspec, {"cnt": np.ones(1)}, enable_split=True, enable_acm=True)
        if near_n:
            assert full and not prunes.all()
        else:
            assert full == (not prunes.any())
            assert prunes.all() or not prunes.any()
        lanes_of.append(list(zip(np.asarray(cspec["gamma"]).tolist(),
                                 np.asarray(cspec["omega"]).tolist())))
    assert len(runs) == (1 if near_n else 2)
    iters = 0
    for schedule, cspec, (final, ofs, merge_iters) in runs:
        ofs = np.asarray(ofs)
        iters += int(np.asarray(merge_iters).sum())
        for lane in range(ofs.shape[0]):
            th = float(np.asarray(cspec["theta"])[lane])
            g = float(np.asarray(cspec["gamma"])[lane])
            om = int(np.asarray(cspec["omega"])[lane])
            want = _oracle_trajectory(trace, th, g, om, t_cg=t_cg,
                                      top_frac=top_frac)
            assert len(want) == nbd
            for w, (b, ref_of) in enumerate(
                    zip(schedule.boundary_steps, want)):
                assert np.array_equal(ofs[lane, int(b)], ref_of), \
                    f"theta={th} gamma={g} omega={om} window={w}"
    assert st["cgm_merge_iters"] == iters > 0
    for c, got in zip(MIXED_GRID, res):
        ref = run_policy(get_policy("akpc", **kw(*c)), trace)
        assert got.n_windows == ref.n_windows == nbd
        assert np.array_equal(got.clique_sizes, ref.clique_sizes)
        a, b = ref.costs.as_dict(), got.costs.as_dict()
        for f in ("n_requests", "n_item_requests", "n_misses", "n_hits",
                  "items_transferred"):
            assert a[f] == b[f], f
        for f in ("transfer", "caching", "keepalive_rent", "total"):
            assert np.isclose(a[f], b[f], rtol=1e-9, atol=0.0), f
