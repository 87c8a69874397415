"""Batched replay == scalar replay, cost-for-cost (engine tentpole).

The batched engine's contract (engine.py module docstring): integer counters
are identical to the per-request scalar loop; float costs agree up to
summation order (we assert 1e-9 relative).  ``batch_size=1`` IS the scalar
loop (handle_request is a batch-of-one wrapper), so it serves as the
reference everywhere.
"""
import math

import numpy as np
import pytest

from repro.core import CostParams, ReplayEngine
from repro.core.baselines import greedy_pair_matching
from repro.traces import SynthConfig, Trace, batch_tensors, synth_trace

INT_FIELDS = ("n_requests", "n_item_requests", "n_misses", "n_hits",
              "items_transferred")
FLOAT_FIELDS = ("transfer", "caching", "keepalive_rent", "total")


def _trace(n_requests=20000, seed=3, m=20, t_max=40.0):
    return synth_trace(SynthConfig(
        kind="netflix", n_items=60, n_servers=m, n_requests=n_requests,
        t_max=t_max, bundle_cover=1.0, bundle_zipf=0.7, seed=seed))


def _pair_gen(n):
    def gen(w_items, w_servers, now):
        del w_servers, now
        return greedy_pair_matching(w_items, n, theta=0.2, top_frac=1.0)
    return gen


def _replay(tr, batch_size, *, t_cg=None, gen=None, charge="requested",
            install_pairs=False):
    eng = ReplayEngine(tr.n, tr.m, CostParams(), caching_charge=charge)
    if install_pairs:
        eng.install_partition(
            greedy_pair_matching(tr.items, tr.n, 0.2, 1.0), now=0.0)
    eng.replay(tr, clique_generator=gen, t_cg=t_cg, batch_size=batch_size)
    return eng.costs


def assert_same_costs(ref, got, rtol=1e-9):
    a, b = ref.as_dict(), got.as_dict()
    for f in INT_FIELDS:
        assert a[f] == b[f], f"{f}: {a[f]} != {b[f]}"
    for f in FLOAT_FIELDS:
        assert np.isclose(a[f], b[f], rtol=rtol, atol=1e-9), \
            f"{f}: {a[f]} != {b[f]}"


@pytest.mark.parametrize("batch_size", [7, 256, 4096])
def test_batched_matches_scalar_static_partition(batch_size):
    """Packed pair cliques, no regeneration: every CostBreakdown field."""
    tr = _trace()
    ref = _replay(tr, 1, install_pairs=True)
    got = _replay(tr, batch_size, install_pairs=True)
    assert ref.n_misses > 0 and ref.n_hits > 0 and ref.keepalive_rent > 0
    assert_same_costs(ref, got)


@pytest.mark.parametrize("batch_size", [64, 256])
def test_batched_matches_scalar_with_tcg_mid_batch(batch_size):
    """Clique regeneration with T_CG boundaries falling mid-batch.

    t_cg = 0.73 never divides the batch grid, so every Event 1 lands inside
    a would-be batch and must split it at exactly the scalar trigger index.
    """
    tr = _trace(n_requests=12000, seed=11)
    gen = _pair_gen(tr.n)
    ref = _replay(tr, 1, t_cg=0.73, gen=gen)
    got = _replay(tr, batch_size, t_cg=0.73, gen=gen)
    assert_same_costs(ref, got)


def test_batched_matches_scalar_stored_accounting():
    tr = _trace(n_requests=8000, seed=5)
    ref = _replay(tr, 1, charge="stored", install_pairs=True)
    got = _replay(tr, 512, charge="stored", install_pairs=True)
    assert_same_costs(ref, got)


def _single_item_trace(times, servers, n=2, m=3):
    R = len(times)
    items = np.zeros((R, 1), dtype=np.int32)
    return Trace(times=np.asarray(times, np.float64),
                 servers=np.asarray(servers, np.int32), items=items,
                 n=n, m=m, name="crafted")


def test_anchor_handoff_within_one_batch():
    """Alg. 6 anchor moves server mid-batch; later same-batch access to the
    old anchor's lapsed copy must MISS (the nasty cross-server case)."""
    tr = _single_item_trace(
        times=[0.0, 5.0, 5.1, 5.2, 9.0], servers=[0, 1, 0, 1, 0])
    ref = _replay(tr, 1)
    got = _replay(tr, 16)        # the whole trace in one batch
    assert_same_costs(ref, got)
    # miss, miss (anchor at 0), MISS (anchor moved to 1), fresh hit, miss
    assert got.n_misses == 4 and got.n_hits == 1


def test_ratchet_rent_within_one_batch():
    """Lapsed-anchor ratcheting (and its lazily-accounted rent) inside a
    batch: gap 3.7 > dt=1 at the same server ratchets 1.0 -> 4.0."""
    tr = _single_item_trace(times=[0.0, 3.7], servers=[0, 0])
    ref = _replay(tr, 1)
    got = _replay(tr, 4)
    assert_same_costs(ref, got)
    assert got.n_misses == 1 and got.n_hits == 1
    assert math.isclose(got.keepalive_rent, 3.0, rel_tol=1e-12)
    assert math.isclose(got.caching, 1.0 + 0.7, rel_tol=1e-12)


def test_batch_tensors_padding_roundtrip():
    tr = _trace(n_requests=1000, seed=9)
    tb = batch_tensors(tr, 128)
    assert tb.n_batches == 8 and tb.batch_size == 128
    assert int(tb.lengths.sum()) == tr.n_requests
    assert (tb.items[-1, int(tb.lengths[-1]):] == -1).all()
    # padded rows are empty requests: replaying the tensors batch-by-batch
    # gives the same costs as the trace, modulo the padded request count
    eng_t = ReplayEngine(tr.n, tr.m, CostParams())
    for b in range(tb.n_batches):
        eng_t.handle_batch(tb.items[b], tb.servers[b], tb.times[b])
    eng_r = ReplayEngine(tr.n, tr.m, CostParams())
    eng_r.replay(tr, batch_size=128)
    pad = tb.n_batches * tb.batch_size - tr.n_requests
    assert eng_t.costs.n_requests == eng_r.costs.n_requests + pad
    eng_t.costs.n_requests -= pad
    assert_same_costs(eng_r.costs, eng_t.costs)


@pytest.mark.slow
def test_batched_matches_scalar_100k():
    """Acceptance: cost-for-cost equality on a seeded 100k-request trace."""
    tr = _trace(n_requests=100_000, seed=0, m=50, t_max=200.0)
    gen = _pair_gen(tr.n)
    ref = _replay(tr, 1, t_cg=3.1, gen=gen)
    got = _replay(tr, 4096, t_cg=3.1, gen=gen)
    assert_same_costs(ref, got)
