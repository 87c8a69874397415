"""The comparison that decides ``correct``: what the timed path produced
against the plain reference (``reference/akpc.py``) on the same requests.

Each configuration states its guarantee: the integer counters and the
clique partition exact, the cost sums within ``cost_rel_tol`` relative of
the reference.  Three numbers are compared, each beside its limit:

* ``counters_off``: the largest absolute difference over the integer
  counters (requests, item requests, misses, hits, items transferred,
  windows); limit 0;
* ``partition_off``: the number of cliques whose size differs, plus the
  difference in clique count; limit 0;
* ``cost_rel_dev``: the largest relative difference over the cost sums
  (transfer, caching, keep-alive rent, total); limit ``cost_rel_tol``.

``PERF.md`` gives the readings each limit was set from.
"""
from __future__ import annotations

import numpy as np

COUNTERS = ("n_requests", "n_item_requests", "n_misses", "n_hits",
            "items_transferred", "n_windows")
COSTS = ("transfer", "caching", "keepalive_rent", "total")


def program_answer(run_result) -> dict:
    """A program ``RunResult`` as the flat dict the reference returns."""
    out = run_result.costs.as_dict()
    out["n_windows"] = int(run_result.n_windows)
    out["clique_sizes"] = np.asarray(run_result.clique_sizes)
    return out


def readings(ref: dict, got: dict) -> dict:
    """The three compared numbers for one answer."""
    counters = max(abs(int(ref[k]) - int(got[k])) for k in COUNTERS)
    a, b = np.asarray(ref["clique_sizes"]), np.asarray(got["clique_sizes"])
    k = min(a.size, b.size)
    part = int((a[:k] != b[:k]).sum()) + abs(a.size - b.size)
    dev = 0.0
    for f in COSTS:
        r, g = float(ref[f]), float(got[f])
        if r != g:
            dev = max(dev, abs(r - g) / max(abs(r), 1e-300))
    return {"counters_off": counters, "partition_off": part,
            "cost_rel_dev": dev}


def limits(guarantee: dict) -> dict:
    return {"counters_off": 0, "partition_off": 0,
            "cost_rel_dev": float(guarantee["cost_rel_tol"])}


def judge(pairs: list, guarantee: dict) -> tuple[bool, dict]:
    """Worst readings over the (reference, program) answer pairs, each
    with its limit, and whether every one is within it."""
    lim = limits(guarantee)
    worst = dict.fromkeys(lim, 0)
    for ref, got in pairs:
        for k, v in readings(ref, got).items():
            worst[k] = max(worst[k], v)
    checks = {k: {"value": worst[k], "limit": lim[k]} for k in lim}
    ok = bool(pairs) and all(worst[k] <= lim[k] for k in lim)
    return ok, checks
