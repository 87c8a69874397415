"""Everything the benchmark takes from the program under test, in one
place: its entry points, its counters and the one private read.

The harness passes a policy only what the configuration defines (the
policy, its cost parameters, ``t_cg`` and ``top_frac``) and the catalog
size; it sets none of the program's path-choosing options (CGM route,
chunk size, ring depth, headroom, state layout, environment variables).
"""
from __future__ import annotations

import os
import queue
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.core import (  # noqa: E402
    CostParams, SweepEngine, SweepPoint, get_policy,
)
from repro.core import cgm_jax, engine_jax  # noqa: E402
from repro.serving import LiveServingEngine  # noqa: E402
from repro.traces import Trace  # noqa: E402


def policy(cfg: dict, **costs):
    """A fresh policy object as the configuration defines it; ``costs``
    overrides cost parameters (a sweep point's alpha and rho)."""
    pol = cfg["policy"]
    return get_policy(pol["name"], params=CostParams(**{
        **cfg["costs"], **costs}), t_cg=pol["t_cg"], top_frac=pol["top_frac"])


def sweep_point(cfg: dict, log, **costs):
    pol = cfg["policy"]
    return SweepPoint(pol["name"], as_trace(log), dict(
        params=CostParams(**{**cfg["costs"], **costs}),
        t_cg=pol["t_cg"], top_frac=pol["top_frac"]))


def as_trace(log) -> Trace:
    """The benchmark's generated log in the program's trace container."""
    return Trace(times=log.times, servers=log.servers, items=log.items,
                 n=log.n, m=log.m)


def scan_traces() -> int:
    """Fresh traces of the replay and fused CGM scans (each one compiles)."""
    return engine_jax.SCAN_TRACES + cgm_jax.SCAN_TRACES


class ChunkWatch:
    """When each dispatched chunk of a ``LiveServingEngine`` completes.

    ``ServeFuture.done()`` cannot say this under sustained load, so this
    adapter makes the benchmark's one private read: after a ``submit``
    that dispatched a chunk, the newest entry of ``engine._probes``, the
    chunk's own non-donated output that the engine's backpressure blocks
    on.  A thread waits on the probes in order and records the host
    clock as each becomes ready; the open loop holds the interpreter's
    switch interval at 0.2 ms while its generator spins.  (Polling the
    probes from the generator at the default 5 ms interval instead made
    each dispatching ``submit`` take about 34 ms rather than under 5 on
    a v5e, and every latency about 30 ms longer.)  The chunk that
    ``drain()`` flushes is taken as complete when ``drain`` returns.

    ``total`` counts the requests submitted before the watch starts.
    ``chunks`` lists ``[lo, hi, dispatched_at, ready_at]``: the chunk
    holds stream requests ``lo <= r < hi``.
    """

    def __init__(self, engine: LiveServingEngine, total: int):
        self.engine = engine
        self.chunks: list[list] = []
        #: True once ``drain`` flushed a remainder as the last chunk
        self.flushed = False
        self._dispatched = total - engine.pending
        self._newest = engine._probes[-1] if engine._probes else None
        self._q: queue.Queue = queue.Queue()
        self._t = threading.Thread(target=self._wait, daemon=True)
        self._t.start()

    def _wait(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            chunk, probe = item
            probe.block_until_ready()
            chunk[3] = time.perf_counter()

    def submitted(self, total: int) -> None:
        """Call after each ``submit``; ``total`` counts every request
        submitted to the engine so far."""
        probes = self.engine._probes
        if probes and probes[-1] is not self._newest:
            self._newest = probes[-1]
            done = total - self.engine.pending
            chunk = [self._dispatched, done, time.perf_counter(), None]
            self.chunks.append(chunk)
            self._dispatched = done
            self._q.put((chunk, self._newest))

    def drain(self, total: int) -> None:
        """``engine.drain()``, with the flushed remainder as a last chunk."""
        t = time.perf_counter()
        self.engine.drain()
        if total > self._dispatched:
            self.chunks.append([self._dispatched, total, t,
                                time.perf_counter()])
            self._dispatched = total
            self.flushed = True
        self.close()

    def close(self) -> None:
        if self._t.is_alive():
            self._q.put(None)
            self._t.join()
