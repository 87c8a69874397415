"""Data pipeline determinism/resume + AKPC expert-cache integration."""
import numpy as np

from repro.data import PackedDataPipeline, ShardStore
from repro.serving import ExpertCacheManager


def test_pipeline_deterministic_and_resumable():
    store = ShardStore(n_shards=32, shard_tokens=256, vocab=100, n_domains=4)
    p1 = PackedDataPipeline(store, batch_rows=4, seq_len=32, seed=5)
    seq = [next(p1) for _ in range(6)]
    p2 = PackedDataPipeline(store, batch_rows=4, seq_len=32, seed=5)
    for _ in range(3):
        next(p2)
    p3 = PackedDataPipeline(store, batch_rows=4, seq_len=32, seed=5)
    p3.load_state_dict({"step": 3})
    for i in range(3):
        np.testing.assert_array_equal(next(p2), seq[3 + i])
        b3 = next(p3)
        np.testing.assert_array_equal(b3, seq[3 + i])


def test_expert_cache_savings():
    """Co-activated experts -> cliques -> AKPC beats per-expert fetching."""
    rng = np.random.default_rng(0)
    mgr = ExpertCacheManager(n_experts=32, n_hosts=4, t_cg=16.0)
    groups = [np.arange(8 * g, 8 * g + 8) for g in range(4)]   # co-activation
    for step in range(400):
        g = groups[int(rng.integers(0, 4) if rng.random() < 0.3 else 0)]
        topk = rng.choice(g, size=(4, 2))
        mgr.observe(topk, host=int(rng.integers(0, 4)))
    stats = mgr.stats()
    assert stats.akpc_total < stats.nopack_total
    assert len(stats.cliques) > 0


def test_expert_cache_snapshot_restore_failover():
    """A standby manager restored from a snapshot keeps observing (the
    manager clock/history must travel with the session state)."""
    rng = np.random.default_rng(7)
    mk = lambda: ExpertCacheManager(n_experts=16, n_hosts=2, t_cg=8.0)
    obs = [(rng.choice(8, size=(3, 2)), int(rng.integers(0, 2)))
           for _ in range(120)]

    primary = mk()
    for topk, host in obs[:60]:
        primary.observe(topk, host=host)
    standby = mk()
    standby.restore(primary.snapshot())
    for mgr in (primary, standby):
        for topk, host in obs[60:]:
            mgr.observe(topk, host=host)
    assert standby.session.costs.as_dict() == primary.session.costs.as_dict()
    assert standby.cliques() == primary.cliques()
    assert standby.stats().nopack_total == primary.stats().nopack_total


def test_packed_tables_layout():
    mgr = ExpertCacheManager(n_experts=8, n_hosts=1, t_cg=4.0)
    rng = np.random.default_rng(1)
    for step in range(40):
        mgr.observe(rng.choice(np.arange(4), size=(2, 2)), host=0)
    w = rng.normal(size=(8, 6)).astype(np.float32)
    table, where = mgr.packed_tables(w)
    for e in range(8):
        ci, slot = where[e]
        np.testing.assert_array_equal(table[ci, slot], w[e])


# ---------------------------------------------------------------------------
# backend="live" routing (PR 7): device-resident session behind the same API
# ---------------------------------------------------------------------------
def test_expert_cache_live_backend_matches_session():
    def run(backend):
        rng = np.random.default_rng(7)
        mgr = ExpertCacheManager(n_experts=16, n_hosts=4, t_cg=8.0,
                                 backend=backend)
        for _ in range(120):
            mgr.observe(rng.integers(0, 16, size=(32, 2)),
                        host=int(rng.integers(0, 4)))
        return mgr

    a, b = run("session"), run("live")
    sa, sb = a.stats(), b.stats()          # stats() drains the live engine
    assert np.isclose(sa.akpc_total, sb.akpc_total, rtol=1e-9)
    assert sa.nopack_total == sb.nopack_total
    assert sa.cliques == sb.cliques

    # checkpoints cross the backend boundary: live -> session
    standby = ExpertCacheManager(n_experts=16, n_hosts=4, t_cg=8.0)
    standby.restore(b.snapshot())
    rng = np.random.default_rng(11)
    obs = [(rng.integers(0, 16, size=(32, 2)), int(rng.integers(0, 4)))
           for _ in range(60)]
    for mgr in (b, standby):
        for topk, host in obs:
            mgr.observe(topk, host=host)
    assert np.isclose(b.stats().akpc_total, standby.stats().akpc_total,
                      rtol=1e-9)


def test_pipeline_live_backend_matches_session():
    def run(backend):
        store = ShardStore(n_shards=64, shard_tokens=256, vocab=100,
                           n_domains=8, seed=0)
        p = PackedDataPipeline(store, batch_rows=8, seq_len=32, t_cg=16.0,
                               backend=backend)
        return p, [next(p) for _ in range(40)]

    p1, o1 = run("session")
    p2, o2 = run("live")
    for x, y in zip(o1, o2):               # token stream is backend-blind
        np.testing.assert_array_equal(x, y)
    p2.cache.drain()
    assert np.isclose(p1.cache.costs.total, p2.cache.costs.total, rtol=1e-9)


def test_unknown_backend_refused():
    import pytest

    with pytest.raises(ValueError):
        ExpertCacheManager(8, 2, backend="bogus")
    with pytest.raises(ValueError):
        PackedDataPipeline(ShardStore(8), batch_rows=2, seq_len=8,
                           backend="bogus")
