"""Alg. 5/6 semantics: the paper's Figure-2 scenario, anchors, translation."""
import math

import numpy as np

from repro.core import CliquePartition, CostParams, ReplayEngine


def _engine(n=6, m=3, **kw):
    return ReplayEngine(n, m, CostParams(**kw.pop("params", {})), **kw)


def test_figure2_scenario():
    """Requests at t, t+0.2, t+0.5, t+0.9 keep d1 cached until t+1.9:
    total caching cost must be exactly 1.9*dt (and one transfer)."""
    eng = _engine()
    t = 5.0
    for ti in (t, t + 0.2, t + 0.5, t + 0.9):
        eng.handle_request([1], 0, ti)
    assert math.isclose(eng.costs.caching, 1.9, rel_tol=1e-9)
    assert eng.costs.n_misses == 1 and math.isclose(eng.costs.transfer, 1.0)
    # expired after t+1.9: next request is a miss again... but Alg. 6 keeps
    # the LAST copy alive (anchor), so at the same server it's a hit
    out = eng.handle_request([1], 0, t + 5.0)
    assert out.misses == []            # last-copy keepalive (Observation 3)
    # at a DIFFERENT server it is a miss
    out = eng.handle_request([1], 1, t + 5.1)
    assert len(out.misses) == 1


def test_packed_transfer_cost():
    eng = _engine()
    part = CliquePartition.from_cliques(6, [(0, 1, 2, 3, 4)])
    eng.install_partition(part, now=0.0)
    out = eng.handle_request([0], 0, 1.0)
    # full 5-clique fetched at discounted cost (1 + 4*0.8)
    assert math.isclose(out.transfer, 1 + 4 * 0.8)
    # clique-mates now cached: hit, no transfer
    out = eng.handle_request([3], 0, 1.5)
    assert out.misses == [] and out.transfer == 0.0


def test_caching_charged_per_requested_item():
    eng = _engine()
    part = CliquePartition.from_cliques(6, [(0, 1, 2, 3, 4)])
    eng.install_partition(part, now=0.0)
    out = eng.handle_request([0, 1], 0, 1.0)     # 2 of 5 items requested
    assert math.isclose(out.caching, 2 * 1.0)    # |D_i| * mu * dt (Thm 1)


def test_stored_accounting():
    eng = ReplayEngine(6, 3, CostParams(), caching_charge="stored")
    part = CliquePartition.from_cliques(6, [(0, 1, 2, 3, 4)])
    eng.install_partition(part, now=0.0)
    out = eng.handle_request([0], 0, 1.0)
    assert math.isclose(out.caching, 5 * 1.0)    # rent for what is stored


def test_expiry_extension_only_charges_delta():
    eng = _engine()
    eng.handle_request([2], 1, 0.0)              # cached till 1.0, pays 1.0
    out = eng.handle_request([2], 1, 0.4)        # extend to 1.4, pays 0.4
    assert math.isclose(out.caching, 0.4)


def test_partition_translation_preserves_presence():
    eng = _engine()
    part1 = CliquePartition.from_cliques(6, [(0, 1)])
    eng.install_partition(part1, now=0.0)
    eng.handle_request([0], 2, 1.0)              # {0,1} cached at server 2
    part2 = CliquePartition.from_cliques(6, [(0, 1)])   # unchanged clique
    eng.install_partition(part2, now=1.2)
    out = eng.handle_request([1], 2, 1.5)
    assert out.misses == []                       # survived regeneration
    # changed clique {0,1,2}: 2 was never cached -> miss
    part3 = CliquePartition.from_cliques(6, [(0, 1, 2)])
    eng.install_partition(part3, now=1.6)
    out = eng.handle_request([0], 2, 1.7)
    assert len(out.misses) == 1


def test_window_seed_servers_matches_dense_argmax():
    """The pair tally picks what a row argmax over the dense (k, m)
    member-access counts picks: duplicates count per occurrence, ties
    and unaccessed cliques take the lowest server."""
    from repro.core.engine import window_seed_servers

    rng = np.random.default_rng(0)
    n, m = 40, 7
    for trial in range(20):
        sizes = rng.integers(1, 4, n)
        cuts = np.cumsum(sizes)
        perm = rng.permutation(n)
        groups = [tuple(int(x) for x in g)
                  for g in np.split(perm, cuts[cuts < n]) if len(g)]
        part = CliquePartition.from_cliques(n, groups)
        R = int(rng.integers(0, 30))
        items = rng.integers(-1, n // 2, (R, 3)).astype(np.int32)
        servers = rng.integers(0, 3, R).astype(np.int32)
        dense = np.zeros((part.k, m), np.int64)
        for row, j in zip(items, servers):
            for it in row[row >= 0]:
                dense[part.clique_of[it], j] += 1
        want = np.argmax(dense, axis=1)
        got = window_seed_servers(m, part, items, servers)
        assert np.array_equal(got, want), trial


def test_seeding_new_cliques():
    eng = _engine()
    w_items = np.array([[0, 1, -1]], np.int32)
    w_servers = np.array([1], np.int32)
    part = CliquePartition.from_cliques(6, [(0, 1)])
    eng.install_partition(part, now=0.0, window_items=w_items,
                          window_servers=w_servers)
    # seeded at the most-active window server (1): first request is a HIT
    out = eng.handle_request([0], 1, 0.5)
    assert out.misses == []
