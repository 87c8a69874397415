"""LiveServingEngine against the numpy reference, policy by policy.

* ``test_live_matches_numpy`` — every registered policy under every
  JAX-expressible cost model, streamed in ragged slices through
  ``chunk_size=512`` device chunks, prices like the numpy ``run_policy``
  of the same trace (counters exact, costs at 1e-9) and leaves the same
  partition and window count;
* ``test_live_snapshot_resumes_bitwise`` — a snapshot taken mid-stream,
  with chunks on the ring and a ragged remainder buffered, restores into
  a fresh engine that finishes bit-identically to an uninterrupted
  stream (``akpc`` is ``test_serving_live``'s own case).
"""
import pytest

from parity import (
    COST_MODELS,
    POLICIES,
    assert_same_costs,
    case,
    policy,
    reference,
    slices,
)
from repro.serving import LiveServingEngine

CHUNK = 512


def _engine(name, model):
    tr, _ = case(model)
    return LiveServingEngine(policy(name, model), tr.n, tr.m,
                             chunk_size=CHUNK)


def _stream(eng, tr, **kw):
    for a, b in slices(tr.n_requests, **kw):
        eng.submit(tr.items[a:b], tr.servers[a:b], tr.times[a:b])


@pytest.mark.parametrize("model", COST_MODELS)
@pytest.mark.parametrize("name", POLICIES)
def test_live_matches_numpy(name, model):
    tr, _ = case(model)
    ref, ref_partition = reference(name, model)
    eng = _engine(name, model)
    _stream(eng, tr)
    eng.drain()
    assert_same_costs(ref.costs, eng.costs)
    assert eng.partition.canonical() == ref_partition
    assert eng.policy.n_windows == ref.n_windows
    assert eng.in_flight == 0 and eng.pending == 0


@pytest.mark.parametrize("name", [p for p in POLICIES if p != "akpc"])
def test_live_snapshot_resumes_bitwise(name):
    tr, _ = case("table1")
    cut = 2503                       # 4 chunks dispatched, 455 buffered
    base = _engine(name, "table1")
    _stream(base, tr)
    base.drain()

    first = _engine(name, "table1")
    _stream(first, tr, hi=cut)
    snap = first.snapshot()          # NOT drained: pending rides along
    second = _engine(name, "table1").restore(snap)
    assert second.pending == cut % CHUNK
    _stream(second, tr, lo=cut)
    second.drain()
    assert_same_costs(base.costs, second.costs, rtol=0.0)
    assert second.partition.canonical() == base.partition.canonical()
    assert second.policy.n_windows == base.policy.n_windows
