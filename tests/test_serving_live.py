"""LiveServingEngine (PR 7): device-resident streaming serving session.

Contracts under test:

* parity — streamed ragged submissions through the live engine price
  EXACTLY like the offline ``run_policy`` replay of the same requests
  (1e-9 relative on float sums, integer counters exact), across chunk
  sizes that exercise tail padding, mid-chunk window boundaries, and
  the single-padded-chunk case;
* one compile — steady-state chunks reuse ONE compiled donated-buffer
  scan (``engine.compiles``, backed by ``engine_jax.SCAN_TRACES``);
  a second engine in the same process compiles NOTHING; the chunked
  ``CacheSession.feed_trace(backend="jax")`` path holds the same bound
  (the PR-7 jit-churn regression);
* snapshot/restore — a snapshot taken MID-FLIGHT (chunks on the ring,
  ragged remainder still buffered) restores into a fresh engine that
  finishes the stream bit-identically to an uninterrupted run, and
  checkpoints compose with the plain ``CacheSession`` path in both
  directions;
* serving surface — futures settle, mid-stream ``costs`` reads see
  completed chunks, out-of-order submissions are refused;
* device-CGM fusion — ``cgm="force"`` (in-scan clique generation,
  PR 6 carry) matches the offline replay and syncs the policy's window
  bookkeeping.
"""
import numpy as np
import pytest

from repro.core import CostParams, get_policy, run_policy
from repro.core import engine_jax as ej
from repro.core.session import CacheSession
from repro.serving import LiveServingEngine
from repro.traces import SynthConfig, synth_trace

PARAMS = CostParams()
T_CG = 0.73                  # never divides the grids: windows split chunks
INT_FIELDS = ("n_requests", "n_item_requests", "n_misses", "n_hits",
              "items_transferred")
FLOAT_FIELDS = ("transfer", "caching", "keepalive_rent", "total")


def _trace(n_requests=4000, seed=3):
    return synth_trace(SynthConfig(
        kind="netflix", n_items=60, n_servers=12, n_requests=n_requests,
        t_max=30.0, bundle_cover=1.0, bundle_zipf=0.7, seed=seed))


def _policy(name="akpc", **kw):
    if name == "akpc":
        kw.setdefault("t_cg", T_CG)
        kw.setdefault("top_frac", 1.0)
    if name == "ttl":
        kw.setdefault("t_cg", T_CG)
    return get_policy(name, params=PARAMS, **kw)


def _stream(eng, trace, seed=0, lo=0, hi=None):
    """Submit [lo, hi) as ragged arrival slices (serving-shaped load)."""
    rng = np.random.default_rng(seed)
    hi = trace.n_requests if hi is None else hi
    while lo < hi:
        k = min(int(rng.integers(1, 300)), hi - lo)
        eng.submit(trace.items[lo:lo + k], trace.servers[lo:lo + k],
                   trace.times[lo:lo + k])
        lo += k


def assert_same_costs(ref, got, exact=False):
    a = ref.as_dict() if not isinstance(ref, dict) else ref
    b = got.as_dict() if not isinstance(got, dict) else got
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
def ref(trace):
    return run_policy(_policy(), trace)


@pytest.fixture(scope="module")
def ref_session(trace):
    s = CacheSession(_policy(), trace.n, trace.m)
    s.feed_trace(trace)
    return s


# ---------------------------------------------------------------------------
# streamed parity vs the offline replay
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk_size", [64, 333, 4096])
def test_live_matches_offline(trace, ref, ref_session, chunk_size):
    eng = LiveServingEngine(_policy(), trace.n, trace.m,
                            chunk_size=chunk_size)
    _stream(eng, trace)
    eng.drain()
    assert_same_costs(ref.costs, eng.costs)
    assert eng.partition.canonical() == ref_session.partition.canonical()
    # a steady-state stream compiles the donated-buffer step (at most)
    # twice: once on the first chunk, plus at most one headroom ratchet
    assert eng.compiles <= 2
    assert eng.in_flight == 0 and eng.pending == 0


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------
def test_live_single_compile_and_warm_reuse(trace, ref):
    cold = LiveServingEngine(_policy(), trace.n, trace.m, chunk_size=512)
    _stream(cold, trace)
    cold.drain()
    assert cold.compiles == 1
    # same process, same shapes: the compiled step is shared via the
    # module-level cache — a warm engine never re-traces
    warm = LiveServingEngine(_policy(), trace.n, trace.m, chunk_size=512)
    _stream(warm, trace, seed=11)       # different slicing, same chunks
    warm.drain()
    assert warm.compiles == 0
    assert_same_costs(ref.costs, warm.costs)


def test_feed_trace_jax_single_compile(trace, ref, monkeypatch):
    """PR-7 regression: chunked ``feed_trace(backend="jax")`` pads ragged
    tail chunks into the ratcheted shape instead of re-tracing per chunk
    (4000 requests / batch 512 = 7 full chunks + a ragged tail)."""
    monkeypatch.setenv("REPRO_JAX_CGM", "off")   # pin the packing path
    before = ej.SCAN_TRACES
    s = CacheSession(_policy(), trace.n, trace.m, batch_size=512,
                     backend="jax")
    s.feed_trace(trace)
    assert ej.SCAN_TRACES - before <= 1
    assert_same_costs(ref.costs, s.costs)
    before = ej.SCAN_TRACES
    s2 = CacheSession(_policy(), trace.n, trace.m, batch_size=512,
                      backend="jax")
    s2.feed_trace(trace)
    assert ej.SCAN_TRACES - before == 0          # fully warm second session


# ---------------------------------------------------------------------------
# snapshot / restore
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk_size,total,cut", [
    (1, 150, 73),          # every request its own device chunk
    (7, 300, 151),         # chunk never aligns with submissions
    (64, 4000, 2503),
    (4096, 4000, 2503),    # single padded tail chunk
])
def test_midflight_snapshot_restores_bitwise(trace, chunk_size, total, cut):
    """Snapshot with chunks ON THE RING and a ragged remainder buffered;
    the restored engine must finish the stream bit-identically to an
    uninterrupted one (the pending buffer travels in the snapshot)."""
    trace = trace.slice(0, total)
    base = LiveServingEngine(_policy(), trace.n, trace.m,
                             chunk_size=chunk_size)
    _stream(base, trace)
    base.drain()

    first = LiveServingEngine(_policy(), trace.n, trace.m,
                              chunk_size=chunk_size)
    _stream(first, trace, hi=cut)
    snap = first.snapshot()              # NOT drained: pending rides along
    second = LiveServingEngine(_policy(), trace.n, trace.m,
                               chunk_size=chunk_size).restore(snap)
    assert second.pending == cut % chunk_size
    _stream(second, trace, lo=cut)
    second.drain()
    assert_same_costs(base.costs, second.costs, exact=True)
    assert second.partition.canonical() == base.partition.canonical()


def test_snapshot_interop_with_cache_session(trace, ref):
    """Checkpoints cross the backend boundary in BOTH directions."""
    cut = 2503
    # live -> plain session (drained live snapshots carry no pending)
    live = LiveServingEngine(_policy(), trace.n, trace.m, chunk_size=512)
    _stream(live, trace, hi=cut)
    live.drain()
    sess = CacheSession(_policy(), trace.n, trace.m)
    sess.restore(live.snapshot())
    sess.feed(trace.items[cut:], trace.servers[cut:], trace.times[cut:])
    assert_same_costs(ref.costs, sess.costs)

    # plain session -> live
    sess2 = CacheSession(_policy(), trace.n, trace.m)
    sess2.feed(trace.items[:cut], trace.servers[:cut], trace.times[:cut])
    live2 = LiveServingEngine(_policy(), trace.n, trace.m, chunk_size=512)
    live2.restore(sess2.snapshot())
    _stream(live2, trace, lo=cut)
    live2.drain()
    assert_same_costs(sess.costs, live2.costs, exact=True)


# ---------------------------------------------------------------------------
# serving surface
# ---------------------------------------------------------------------------
def test_futures_and_midstream_costs(trace, ref):
    eng = LiveServingEngine(_policy(), trace.n, trace.m, chunk_size=256)
    cut = 1000
    fut = eng.submit(trace.items[:cut], trace.servers[:cut],
                     trace.times[:cut])
    # 3 full chunks dispatched, 232 requests still buffered
    assert eng.pending == cut % 256
    assert not fut.done()
    mid = eng.costs                      # completed chunks only — readable
    assert mid.n_requests <= cut         # without flushing the buffer
    got = fut.result()                   # flushes: every request priced
    assert fut.done()
    prefix_ref = run_policy(_policy(), trace.slice(0, cut))
    assert_same_costs(prefix_ref.costs, got)
    _stream(eng, trace, lo=cut)
    assert_same_costs(ref.costs, eng.result().costs)


def test_out_of_order_submission_refused(trace):
    eng = LiveServingEngine(_policy(), trace.n, trace.m)
    eng.submit(trace.items[:10], trace.servers[:10], trace.times[:10])
    with pytest.raises(ValueError):
        eng.submit(trace.items[:5], trace.servers[:5],
                   trace.times[:5] - 100.0)
    with pytest.raises(ValueError):
        LiveServingEngine(_policy(), trace.n, trace.m, cgm="sometimes")


# ---------------------------------------------------------------------------
# device-CGM fusion (PR 6 carry inside the serving loop)
# ---------------------------------------------------------------------------
def test_live_cgm_force_matches_offline():
    trace = _trace(n_requests=1500)
    ref = run_policy(_policy(), trace)
    eng = LiveServingEngine(_policy(), trace.n, trace.m, chunk_size=512,
                            cgm="force")
    assert eng._cgm                      # eligibility gate actually passed
    _stream(eng, trace)
    eng.drain()
    assert_same_costs(ref.costs, eng.costs)
    sess = CacheSession(_policy(), trace.n, trace.m)
    sess.feed_trace(trace)
    assert eng.partition.canonical() == sess.partition.canonical()
    assert eng.policy.n_windows == ref.n_windows


def test_live_cgm_auto_routes_device_on_cpu():
    """DESIGN.md §15: ``cgm="auto"`` fuses clique generation into the
    serving scan on EVERY backend — the compact hot space removed the
    accelerator-kernel requirement, so plain CPU routes device too.
    Row-sharded state is the one remaining fallback."""
    import jax

    from repro.core.state_layout import StateLayout

    trace = _trace(n_requests=1500)
    assert jax.default_backend() == "cpu"    # the lane this gate is about
    eng = LiveServingEngine(_policy(), trace.n, trace.m, chunk_size=512)
    assert eng._cgm                          # auto flipped ON, no kernels
    _stream(eng, trace)
    eng.drain()
    ref = run_policy(_policy(), trace)
    assert_same_costs(ref.costs, eng.costs)
    assert eng.policy.n_windows == ref.n_windows

    # explicit off and ineligible policies still fall back to the host
    assert not LiveServingEngine(_policy(), trace.n, trace.m,
                                 cgm="off")._cgm
    assert not LiveServingEngine(_policy("ttl"), trace.n, trace.m)._cgm
    # row-sharded state: the in-scan reductions need unsharded rows
    sharded = StateLayout(kind="row_sharded", shards=3)
    assert not LiveServingEngine(_policy(), trace.n, trace.m,
                                 layout=sharded)._cgm
