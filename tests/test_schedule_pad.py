"""``schedule_dims``/``pad_schedule`` edge cases (sweep shape alignment).

The SweepEngine pads every schedule of a cohort up to common dims (and,
since the cross-run dims ratchet, up to the largest dims the process has
seen) — so padding must be exactly semantics-free on the degenerate
shapes real grids produce:

* zero-event windows — T_CG boundaries firing across a request gap, so
  install steps carry no (or collapsed) event batches;
* a single ragged chunk — batch size far above the trace length, one
  partially-filled scan step;
* n=1 catalogs — a one-item catalog where every partition is the
  singleton partition and every install is trivial.

Each case asserts (a) the unpadded jax replay matches the numpy engine
and (b) replaying the PADDED schedule reproduces the unpadded
accumulator bit-for-bit (padded steps/slots are inert).
"""
import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import CostParams, get_policy, run_policy
from repro.core.cliques import CliquePartition
from repro.core.cost import CacheEnvironment, CostBreakdown, get_cost_model
from repro.core import engine_jax as ej
from repro.traces import Trace

PARAMS = CostParams()
INT_FIELDS = ("n_requests", "n_item_requests", "n_misses", "n_hits",
              "items_transferred")
FLOAT_FIELDS = ("transfer", "caching", "keepalive_rent", "total")


def _mk_trace(times, servers, items, n, m):
    d = max(len(d_i) for d_i in items)
    arr = np.full((len(items), d), -1, np.int32)
    for i, d_i in enumerate(items):
        arr[i, : len(d_i)] = d_i
    return Trace(
        times=np.asarray(times, np.float64),
        servers=np.asarray(servers, np.int32),
        items=arr, n=n, m=m, name="edge")


def _build(policy_name, trace, *, t_cg=None, batch_size=None, **kw):
    if t_cg is not None:
        kw["t_cg"] = t_cg
    policy = get_policy(policy_name, params=PARAMS, **kw)
    policy.bind(trace.n, trace.m)
    env = CacheEnvironment.resolve(None, trace, policy.params)
    model = get_cost_model("table1", env)
    spec, statics = ej.cost_spec(model, env)
    part0 = CliquePartition.singletons(trace.n)
    gen = policy.on_window if policy.t_cg is not None else None
    sched = ej.build_schedule(
        part0, trace, gen, policy.t_cg, model=model, env=env,
        batch_size=batch_size)
    return policy, sched, spec, statics


def _replay(sched, spec, statics, charge="requested"):
    E0, a0 = ej.fresh_state_arrays(sched.n, sched.m)
    E, anchor, acc = ej.run_schedule(sched, spec, statics, E0, a0,
                                     charge=charge)
    costs = CostBreakdown(model=statics[0])
    ej.apply_acc(costs, sched, acc)
    return E, anchor, acc, costs


def _assert_costs(ref, got):
    a, b = ref.as_dict(), got.as_dict()
    for f in INT_FIELDS:
        assert a[f] == b[f], f"{f}: {a[f]} != {b[f]}"
    for f in FLOAT_FIELDS:
        assert np.isclose(a[f], b[f], rtol=1e-9, atol=1e-9), \
            f"{f}: {a[f]} != {b[f]}"


def _pad_and_check(sched, spec, statics, boost):
    """Padding up by ``boost`` must not change the state at all, nor the
    integer accumulator slots; the float cost slots (transfer, caching,
    rent) are sums whose reduction order XLA may pick per shape, so they
    are held to the repo's 1e-9 relative bar."""
    E, anchor, acc, _ = _replay(sched, spec, statics)
    dims = {k: v + boost for k, v in ej.schedule_dims(sched).items()}
    padded = ej.pad_schedule(sched, dims)
    assert ej.schedule_dims(padded) == dims
    Ep, ap, accp, _ = _replay(padded, spec, statics)
    np.testing.assert_array_equal(acc[3:], accp[3:])
    np.testing.assert_allclose(accp[:3], acc[:3], rtol=1e-9, atol=0)
    np.testing.assert_array_equal(E, Ep)
    np.testing.assert_array_equal(anchor, ap)


def test_pad_schedule_noop_when_dims_equal():
    tr = _mk_trace([0.0, 0.1, 0.2], [0, 1, 0], [[0, 1], [1], [0]], 3, 2)
    _, sched, spec, statics = _build("akpc", tr, t_cg=0.15)
    assert ej.pad_schedule(sched, ej.schedule_dims(sched)) is sched


def test_zero_event_windows():
    """A request gap spanning several T_CG periods: boundaries collapse
    onto the next request, install steps ride along, padding stays inert."""
    times = [0.0, 0.05, 0.1, 0.15, 5.0, 5.05, 5.1]     # gap >> t_cg
    servers = [0, 1, 0, 1, 0, 1, 0]
    items = [[0, 1], [0, 1], [2], [0, 1], [2, 3], [2, 3], [0]]
    tr = _mk_trace(times, servers, items, 4, 2)
    policy, sched, spec, statics = _build("akpc", tr, t_cg=0.2)
    _, _, _, costs = _replay(sched, spec, statics)
    ref = run_policy(get_policy("akpc", params=PARAMS, t_cg=0.2), tr)
    _assert_costs(ref.costs, costs)
    _pad_and_check(sched, spec, statics, 3)


def test_single_ragged_chunk():
    """batch size far above the trace length: one partially-filled step."""
    rng = np.random.default_rng(0)
    R, n, m = 37, 8, 3
    times = np.sort(rng.uniform(0, 2.0, R))
    servers = rng.integers(0, m, R)
    items = [list(rng.choice(n, rng.integers(1, 4), replace=False))
             for _ in range(R)]
    tr = _mk_trace(times, servers, items, n, m)
    policy, sched, spec, statics = _build(
        "akpc", tr, t_cg=0.7, batch_size=4096)
    _, _, _, costs = _replay(sched, spec, statics)
    ref = run_policy(get_policy("akpc", params=PARAMS, t_cg=0.7), tr,
                     batch_size=4096)
    _assert_costs(ref.costs, costs)
    _pad_and_check(sched, spec, statics, 5)


@pytest.mark.parametrize("name,kw", [
    ("akpc", {"t_cg": 0.3}),
    ("no_packing", {}),
])
def test_n1_catalog(name, kw):
    """One-item catalog: every window re-installs the singleton partition."""
    times = [0.0, 0.2, 0.4, 0.9, 1.3, 1.31]
    servers = [0, 1, 0, 1, 0, 1]
    items = [[0]] * 6
    tr = _mk_trace(times, servers, items, 1, 2)
    policy, sched, spec, statics = _build(name, tr, **kw)
    assert sched.n == 1
    _, _, _, costs = _replay(sched, spec, statics)
    ref = run_policy(get_policy(name, params=PARAMS, **kw), tr)
    _assert_costs(ref.costs, costs)
    _pad_and_check(sched, spec, statics, 2)
