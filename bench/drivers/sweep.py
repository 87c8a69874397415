"""Driver for ``SweepEngine.run``: what-if pricing over a scenario grid.

Traffic keys: ``alpha`` and ``rho`` as ``[first, last, count]`` (the grid
is every pair, alpha fastest, as the fig6 sensitivity grid), and the
logs: ``trace_requests`` per log and ``traces`` distinct logs drawn from
the seed, each priced once in set-up, so that the window compiles
nothing, and then in turn in the window, one ``SweepEngine().run`` call
over the whole grid per log.  The rate counts scenario-requests: points
times requests.  ``correct`` compares every point of the grid, each
from a call of the window drawn from the seed, so every vmapped lane is
checked in every run.
"""
from __future__ import annotations

import time

import numpy as np

import compare
import gen
import program


def grid(tr: dict) -> list:
    """The (alpha, rho) points, alpha fastest."""
    alphas = np.linspace(*tr["alpha"][:2], int(tr["alpha"][2]))
    rhos = np.linspace(*tr["rho"][:2], int(tr["rho"][2]))
    return [{"alpha": float(a), "rho": float(r)} for r in rhos
            for a in alphas]


def _run(cfg, pts, log) -> list:
    return program.SweepEngine().run(
        [program.sweep_point(cfg, log, **p) for p in pts])


def draw(cell) -> list:
    """The logs this cell prices, drawn from its seed (host only)."""
    cfg, tr = cell.cfg, cell.traffic
    n, m = cfg["catalog"]["n_items"], cfg["catalog"]["n_servers"]
    R = int(tr["trace_requests"])
    t_max = R * cfg["trace"]["time_per_request"]
    return [gen.trace(cfg["trace"], n, m, R, t_max,
                      gen.rng_for(cell.seed, 0, i))
            for i in range(int(tr["traces"]))]


def setup(cell) -> dict:
    cfg, logs, pts = cell.cfg, draw(cell), grid(cell.traffic)
    for log in logs:
        _run(cfg, pts, log)
    return {"cell": cell, "pts": pts, "logs": logs, "done": []}


def window(st: dict, seconds: float, span) -> dict:
    cfg, logs, pts = st["cell"].cfg, st["logs"], st["pts"]
    t0 = time.perf_counter()
    n = i = 0
    while time.perf_counter() - t0 < seconds:
        log = logs[i % len(logs)]
        with span("sweep_call"):
            res = _run(cfg, pts, log)
        st["done"].append((i % len(logs), res))
        n += log.n_requests * len(pts)
        i += 1
    elapsed = time.perf_counter() - t0
    return {"attempted": n, "elapsed_s": elapsed,
            "metrics": {"req_per_s": n / elapsed},
            "harness": {"requests": n, "calls": i}}


def answers(st: dict) -> list:
    cell, pts, done = st["cell"], st["pts"], st["done"]
    calls = gen.rng_for(cell.seed, 1).integers(0, len(done), size=len(pts))
    out = []
    for p, call in enumerate(calls):
        li, res = done[call]
        out.append((st["logs"][li], {**cell.cfg["costs"], **pts[p]},
                    compare.program_answer(res[p])))
    return out


def reference_inputs(cell, requests: int) -> list:
    """What a run compares, drawn without the program: every point of
    the first log."""
    del requests
    log = draw(cell)[0]
    return [(log, {**cell.cfg["costs"], **p}) for p in grid(cell.traffic)]
