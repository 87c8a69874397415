"""AKPC (the paper's Alg. 1) on the copied numpy engine: the plain
reference every cell's ``correct`` is decided against.

It mirrors the program's numpy ``run_policy`` with ``AKPCPolicy``: at every
``t_cg`` boundary the previous window's requests build the CRM (Alg. 2),
the previous cliques are adjusted (Alg. 4), split and approximately merged
(Alg. 3), and the engine installs the new partition; requests are priced
by Alg. 5/6 in between.  It imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

from . import engine
from .cliques import generate_cliques
from .cost import CacheEnvironment, CostParams
from .crm import build_window_crm


class AKPC:
    """The clique generator at each window boundary (Alg. 1, Event 1)."""

    def __init__(self, n: int, params: CostParams, top_frac: float):
        self.n = n
        self.params = params
        self.top_frac = top_frac
        self.partition = None
        self.prev_crm = None
        self.n_windows = 0

    def on_window(self, items, servers, now):
        del servers, now
        p = self.params
        crm = build_window_crm(items, self.n, p.theta, self.top_frac)
        self.partition = generate_cliques(
            self.partition, self.prev_crm, crm, self.n, p.omega, p.gamma)
        self.prev_crm = crm
        self.n_windows += 1
        return self.partition


def run(log, costs: dict, policy: dict, float_type=np.float64) -> dict:
    """Replay ``log`` under AKPC; returns the counters, the cost sums and
    the final clique sizes.  ``float_type`` is float64 as configured, or
    float32 for the precision control."""
    if policy["name"] != "akpc":
        raise ValueError(f"the reference has no policy {policy['name']!r}")
    params = CostParams(**costs)
    prev = engine.FLOAT
    engine.FLOAT = float_type
    try:
        gen = AKPC(log.n, params, policy["top_frac"])
        eng = engine.ReplayEngine(
            log.n, log.m, params, env=CacheEnvironment(log.n, log.m, params))
        eng.replay(log, clique_generator=gen.on_window, t_cg=policy["t_cg"])
    finally:
        engine.FLOAT = prev
    out = eng.costs.as_dict()
    out["n_windows"] = gen.n_windows
    out["clique_sizes"] = eng.state.partition.sizes()
    return out
