"""AKPC as a MoE expert cache: seeded, skewed top-k routings stream into
``ExpertCacheManager``, which packs co-activated experts into cliques and
reports the transfer-cost saving vs per-expert fetching.

Routing is synthetic: experts fall into topic groups, each decode step
draws its tokens' topics from a Zipf popularity that drifts over time, and
a token's top-k mixes experts of its topic with a few globally popular
ones.  Same seed, same routings.

    PYTHONPATH=src python examples/serve_moe_expert_cache.py
"""
import numpy as np

from repro.serving import ExpertCacheManager

N_EXPERTS, TOP_K, N_HOSTS = 40, 8, 2
GROUP = 5                      # experts per topic group (= omega)
TOKENS, STEPS, DRIFT = 4, 240, 60


def routings(seed: int = 0):
    """Yield (host, (TOKENS, TOP_K) expert ids) per decode step."""
    rng = np.random.default_rng(seed)
    groups = rng.permutation(N_EXPERTS).reshape(-1, GROUP)
    zipf = 1.0 / np.arange(1, len(groups) + 1) ** 1.2
    glob = 1.0 / np.arange(1, N_EXPERTS + 1) ** 0.8
    glob /= glob.sum()
    for step in range(STEPS):
        if step % DRIFT == 0:              # topic drift: reshuffle popularity
            pop = rng.permutation(zipf / zipf.sum())
        topk = np.empty((TOKENS, TOP_K), np.int64)
        for t in range(TOKENS):
            own = groups[rng.choice(len(groups), p=pop)]
            rest = np.setdiff1d(np.arange(N_EXPERTS), own)
            p = glob[rest] / glob[rest].sum()
            topk[t] = np.concatenate(
                [own, rng.choice(rest, TOP_K - GROUP, replace=False, p=p)])
        yield int(rng.integers(0, N_HOSTS)), topk


def main():
    mgr = ExpertCacheManager(n_experts=N_EXPERTS, n_hosts=N_HOSTS, t_cg=24.0)
    for host, topk in routings(seed=0):
        mgr.observe(topk, host=host)

    stats = mgr.stats()
    print(f"expert-cache: {stats.n_observations} routing observations, "
          f"{len(stats.cliques)} expert cliques: {stats.cliques[:6]}")
    print(f"AKPC packed-expert cost {stats.akpc_total:.1f} vs per-expert "
          f"{stats.nopack_total:.1f}  ->  {stats.saving_pct:.1f}% saved")

    # pack the expert weights per clique for single-DMA gathers
    w = np.random.default_rng(1).normal(size=(N_EXPERTS, 64)).astype(np.float32)
    table, where = mgr.packed_tables(w)
    print(f"packed table: {table.shape} (cliques x omega x expert row)")


if __name__ == "__main__":
    main()
