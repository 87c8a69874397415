"""Driver for ``SweepEngine.run`` over the paper's Fig. 7 clique knobs.

Traffic keys: ``theta``, ``gamma`` and ``omega``, each a list of axis
values.  The grid varies one axis at a time through the configuration's
own point (its ``costs``), duplicates dropped, as Fig. 7 plots them; the
pricing parameters stay the configuration's.  The logs
(``trace_requests``, ``traces``), the window of whole grid calls and the
check of every grid point, each from a call of the window drawn from
the seed, are those of ``drivers/sweep.py``, whose helpers this driver
uses.  ``harness`` carries the engine's ``stats`` over the window's
calls: counts summed, sizes (``cgm_hot``, ``cgm_merge_space``,
``cgm_window_rows``) at their largest; a program without ``stats``
leaves them out.
"""
from __future__ import annotations

import time

import program
from drivers import sweep

AXES = ("theta", "gamma", "omega")
#: engine stats that are sizes, not counts
SIZES = ("cgm_hot", "cgm_merge_space", "cgm_window_rows")


def grid(cfg: dict, tr: dict) -> list:
    """Each axis of ``tr`` through the configuration's point, in axis
    order, every distinct point once."""
    centre = {k: cfg["costs"][k] for k in AXES}
    pts: list = []
    for axis in AXES:
        for v in tr[axis]:
            p = {**centre, axis: v}
            if p not in pts:
                pts.append(p)
    return pts


def _run(cfg, pts, log, stats: dict) -> list:
    eng = program.SweepEngine()
    res = eng.run([program.sweep_point(cfg, log, **p) for p in pts])
    for k, v in getattr(eng, "stats", {}).items():
        stats[k] = max(stats.get(k, 0), v) if k in SIZES \
            else stats.get(k, 0) + v
    return res


def setup(cell) -> dict:
    cfg, logs, pts = cell.cfg, sweep.draw(cell), grid(cell.cfg, cell.traffic)
    for log in logs:
        _run(cfg, pts, log, {})
    return {"cell": cell, "pts": pts, "logs": logs, "done": []}


def window(st: dict, seconds: float, span) -> dict:
    cfg, logs, pts = st["cell"].cfg, st["logs"], st["pts"]
    stats: dict = {}
    t0 = time.perf_counter()
    n = i = 0
    while time.perf_counter() - t0 < seconds:
        log = logs[i % len(logs)]
        with span("sweep_call"):
            res = _run(cfg, pts, log, stats)
        st["done"].append((i % len(logs), res))
        n += log.n_requests * len(pts)
        i += 1
    elapsed = time.perf_counter() - t0
    return {"attempted": n, "elapsed_s": elapsed,
            "metrics": {"req_per_s": n / elapsed},
            "harness": {"requests": n, "calls": i, "points": i * len(pts),
                        **stats}}


answers = sweep.answers


def reference_inputs(cell, requests: int) -> list:
    """What a run compares, drawn without the program: every point of
    the first log."""
    del requests
    log = sweep.draw(cell)[0]
    return [(log, {**cell.cfg["costs"], **p})
            for p in grid(cell.cfg, cell.traffic)]
