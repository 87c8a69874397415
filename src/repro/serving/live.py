"""Persistent on-device serving engine (DESIGN.md §12).

``CacheSession.feed_trace(backend="jax")`` is replay machinery: every
chunk rebuilds a schedule, uploads the full cache state, scans, and
downloads it again.  :class:`LiveServingEngine` is the serving-grade
counterpart — a session whose cache state NEVER leaves the device
between chunks:

* **One compiled step, donated buffers.**  The first chunk fixes the
  padded event-tensor shape (with headroom); every later chunk pads
  into it, so XLA compiles the scan exactly once.  The carry
  (expiry matrix, anchors, cost accumulator) is donated to the jit'd
  step, letting XLA update it in place instead of allocating a fresh
  state per chunk.
* **Async chunk ring.**  Dispatch is non-blocking: the host packs
  chunk k+1's event tensors (``build_schedule`` — argsorts, window
  bookkeeping, clique generation) while the device executes chunk k.
  A small ring of in-flight chunks bounds the lag; submitting past it
  blocks on the oldest chunk (backpressure).
* **Absolute cost accumulator.**  The device accumulator is seeded
  from the session's cost breakdown, so mid-stream ``costs`` reads are
  a 6-float download — no state round-trip, and bitwise-exact on
  resume because f64 totals travel through snapshots unrounded.

Requests enter through :meth:`submit` (buffered into fixed-size
chunks; returns a :class:`ServeFuture`), and :meth:`drain` flushes the
ragged remainder, blocks the ring, and syncs the numpy engine — after
which the wrapped :class:`~repro.core.session.CacheSession` is
indistinguishable from one that replayed the same requests itself:
:meth:`snapshot`/:meth:`restore` compose bitwise with the plain
session checkpoint path in both directions (a live snapshot taken
mid-stream carries the un-dispatched request buffer along).

The engine is duck-compatible with ``CacheSession`` (``feed``,
``costs``, ``partition``, ``now``, ``snapshot``/``restore``,
``result``), so :mod:`repro.serving.expert_cache` and
:mod:`repro.data.pipeline` route through it with a ``backend="live"``
switch.
"""
from __future__ import annotations

import time as _time
import functools
import warnings
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.cost import CostBreakdown
from ..core.engine import CacheState
from ..core.policy import RunResult
from ..core.session import CacheSession
from ..core import engine_jax as ej

# buffer donation is an optimization; backends that cannot donate (some
# CPU configurations) fall back to copying and warn — harmless here
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


class _Chunk:
    """Duck-typed request container for the schedule builders."""

    __slots__ = ("items", "servers", "times", "n_requests", "n", "m",
                 "d_max")

    def __init__(self, items, servers, times, n=0, m=0):
        self.items = items
        self.servers = servers
        self.times = times
        self.n_requests = int(times.shape[0])
        self.n = n
        self.m = m
        self.d_max = int(items.shape[1]) if items.ndim == 2 else 1


@functools.lru_cache(maxsize=None)
def _compiled_live_step(statics, charge, const_dt):
    """jit'd scan step with a DONATED carry.

    Returns ``((E, anchor, acc), probe)``: the carry buffers are donated
    (arg 1), so they cannot be waited on from the host — the ring blocks
    on the small non-donated ``probe`` scalar instead.
    """
    base = functools.partial(
        ej._replay_impl, kind=statics, charge=charge, const_dt=const_dt)

    def step(spec, carry, xs):
        E, anchor, acc = base(spec, carry, xs)
        return (E, anchor, acc), acc[0] + acc[1]

    return jax.jit(step, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _compiled_cgm_live_step(statics, charge, uses_sizes, enable_split,
                            enable_acm, seed_new, use_kernels, gcap,
                            full_merge):
    """jit'd fused CGM+replay scan step with a DONATED carry dict.

    The per-step clique slot maps (``ofs``) double as the ring probe:
    they are a regular (non-donated) output, so the host can block on
    them, and they feed ``policy.size_history`` at sync time.
    ``gcap`` / ``full_merge`` are the compile-time loop capacities from
    ``cgm_jax.cgm_loop_statics``, fixed at carry creation.
    """
    from ..core import cgm_jax

    base = functools.partial(
        cgm_jax._cgm_replay_impl, kind=statics, charge=charge,
        uses_sizes=uses_sizes, enable_split=enable_split,
        enable_acm=enable_acm, seed_new=seed_new, use_kernels=use_kernels,
        gcap=gcap, full_merge=full_merge)

    def step(spec, cspec, carry, xs, sizes):
        return base(spec, cspec, carry, xs, sizes)

    return jax.jit(step, donate_argnums=(2,))


class ServeFuture:
    """Handle for one :meth:`LiveServingEngine.submit` call.

    ``result()`` guarantees every request of that call has been priced
    (flushing the pending buffer if needed) and returns the synced cost
    breakdown.  Futures are invalidated by :meth:`restore`.
    """

    __slots__ = ("_eng", "_upto")

    def __init__(self, eng: "LiveServingEngine", upto: int):
        self._eng = eng
        self._upto = upto

    def done(self) -> bool:
        """True once the chunk holding this submit's last request is ready
        on the device; False while that request is still buffered."""
        e = self._eng
        if e._dispatched_total < self._upto:
            return False
        if self._upto <= e._ready_total:
            return True
        for end, probe in zip(e._probe_ends, e._probes):
            if self._upto <= end:
                return probe.is_ready()
        return True

    def result(self) -> CostBreakdown:
        e = self._eng
        if e._dispatched_total < self._upto:
            e._flush()
        with obs.span("live.sync", call="result"):
            e._block()
            return e._sync_costs()


class LiveServingEngine:
    """Device-resident streaming session (see module docstring).

    Parameters
    ----------
    policy, n, m, env, batch_size : as for ``CacheSession``.
    chunk_size : requests per compiled device step.  Submissions are
        buffered until a full chunk accumulates; tail chunks (``drain``)
        pad into the same shape with masked no-op events.
    ring : maximum chunks in flight before ``submit`` blocks on the
        oldest one (host/device overlap depth).
    headroom : multiplier applied to the first chunk's event-tensor
        dims when fixing the compiled shape.  A later chunk that still
        outgrows it ratchets the dims (one recompile, counted in
        ``compiles``); default 2.0 keeps steady-state streams on a
        single compile.
    cgm : ``"auto"`` (default) fuses clique generation into the device
        scan when the policy/catalog pass ``wants_device_cgm`` — the
        host then ships only raw request tensors and pays zero
        clique-generation calls.  The compact hot-space boundary
        (DESIGN.md §15) made this the winning path on EVERY backend:
        CPU lanes run the same fused scan through jnp twins of the
        Mosaic kernels, so auto no longer falls back off-TPU.
        ``"force"`` keeps its meaning (assert fusion, error if
        ineligible via the carry checks); ``"off"`` disables fusion.
    """

    def __init__(self, policy, n, m, *, env=None, batch_size=None,
                 chunk_size=32768, ring=4, headroom=2.0, cgm="auto",
                 layout=None):
        ej.enable_compile_cache()
        self.session = CacheSession(
            policy, n, m, env=env, batch_size=batch_size, layout=layout)
        #: device state geometry (dense / bucketed / row_sharded)
        self.layout = self.session.layout
        # validates the cost model has device hooks, builds spec/statics
        self._jeng = ej.JaxReplayEngine(
            engine=self.session.engine, layout=self.layout)
        self.policy = self.session.policy
        self.n, self.m = n, m
        self.chunk_size = max(1, int(chunk_size))
        self.ring = max(1, int(ring))
        self.headroom = float(headroom)
        self._part = self.session.partition
        self._carry = None          # (E, anchor, acc) device arrays
        self._spec_j = None         # device copy of the scenario spec
        self._probes: deque = deque()
        #: request total at the end of each in-flight chunk (with _probes)
        self._probe_ends: deque = deque()
        #: requests of every chunk known to be ready on the device
        self._ready_total = 0
        self._dims: dict | None = None
        #: fresh scan traces (= XLA compiles) triggered by this engine
        self.compiles = 0
        self._n = dict(chunks=0, requests=0, ring_waits=0, ring_wait_s=0.0,
                       h2d_bytes=0, carry_grows=0)
        self._pend: list[tuple] = []     # (items, servers, times) buffers
        self._pend_n = 0
        self._submitted_total = 0
        self._dispatched_total = 0
        self._last_sub = -np.inf
        self._base_req = (0, 0)     # (n_requests, n_item_requests) at seed
        self._host_nreq = 0
        self._host_nitem = 0
        self._acc_dirty = False
        # device-CGM mode (PR 6 fused scan, persistent carry dict)
        if cgm not in ("auto", "force", "off"):
            raise ValueError(f"unknown cgm mode {cgm!r}")
        self._cgm = False
        if cgm != "off":
            from ..core.cgm_jax import wants_device_cgm

            eligible = wants_device_cgm(
                self.policy,
                _Chunk(np.zeros((0, 1), np.int64), np.zeros(0, np.int64),
                       np.zeros(0, np.float64), n, m),
                self.session.engine.model)
            # the fused CGM carry is dense-n on its own whatever the
            # session layout; only row-sharded state falls back — and
            # the compact workspace means NO backend check: CPU fuses
            # through the jnp kernel twins (DESIGN.md §15)
            self._cgm = (eligible
                         and self.layout.supports_device_cgm(n, m))
        self._cgm_carry = None      # device carry dict (E..of..crm..pbin)
        self._cgm_dims = None       # ratcheted (nb, B, d, h, W) chunk shape
        self._cgm_statics = None    # (gcap, full_merge) loop capacities
        self._cspec_j = None
        self._sz_j = None
        self._ofs: list[tuple] = []  # (boundary_steps, ofs_dev) per chunk
        self._cgm_bound = False      # any boundary since carry init?

    # -- views -------------------------------------------------------------
    @property
    def partition(self):
        """Partition after the last DISPATCHED window boundary."""
        return self._part

    @property
    def now(self) -> float:
        """Time of the most recently submitted request (-inf before any)."""
        return max(self._last_sub, self.session._last_t)

    @property
    def in_flight(self) -> int:
        """Chunks currently executing on device."""
        return len(self._probes)

    @property
    def pending(self) -> int:
        """Buffered requests not yet dispatched (less than one chunk)."""
        return self._pend_n

    @property
    def stats(self) -> dict:
        """Counters over this engine's life: ``chunks`` dispatched and the
        ``requests`` in them, ``ring_waits`` (dispatches that blocked on
        the oldest in-flight chunk) and their ``ring_wait_s``,
        ``h2d_bytes`` of event tensors put on the device, ``carry_grows``
        of the CGM carry, and ``compiles``."""
        return dict(self._n, compiles=self.compiles)

    @property
    def costs(self) -> CostBreakdown:
        """Mid-stream costs of every COMPLETED chunk (blocks the ring;
        the < chunk_size buffered requests are priced at :meth:`drain`)."""
        with obs.span("live.sync", call="costs"):
            return self._sync_costs()

    # -- streaming ---------------------------------------------------------
    def submit(self, items, servers, times) -> ServeFuture:
        """Enqueue one time-ordered request chunk; returns a future.

        Arguments as for ``CacheSession.feed``: ``items`` (R, d) int with
        -1 padding (1-D = single-item requests), ``servers`` (R,),
        ``times`` (R,) non-decreasing and >= every earlier submission.
        Full ``chunk_size`` chunks dispatch asynchronously; the call only
        blocks when more than ``ring`` chunks are already in flight.
        """
        t0 = _time.perf_counter()
        items = np.atleast_2d(np.asarray(items))
        servers = np.asarray(servers, dtype=np.int64).reshape(-1)
        times = np.asarray(times, dtype=np.float64).reshape(-1)
        R = times.shape[0]
        if R == 0:
            return ServeFuture(self, self._submitted_total)
        if items.shape[0] != R or servers.shape[0] != R:
            raise ValueError(
                f"chunk shape mismatch: items {items.shape}, "
                f"servers {servers.shape}, times {times.shape}")
        if (np.diff(times) < 0).any() or times[0] < self._last_sub:
            raise ValueError(
                "requests must be submitted in non-decreasing time order")
        self._last_sub = float(times[-1])
        self._pend.append((items, servers, times))
        self._pend_n += R
        self._submitted_total += R
        while self._pend_n >= self.chunk_size:
            self._dispatch(*self._pop_chunk(self.chunk_size))
        self.session._wall += _time.perf_counter() - t0
        return ServeFuture(self, self._submitted_total)

    def feed(self, items, servers, times) -> CostBreakdown:
        """``CacheSession.feed``-compatible alias of :meth:`submit`.

        Returns the live breakdown object WITHOUT forcing a device sync —
        read :attr:`costs` (or call :meth:`drain`) for settled numbers.
        """
        self.submit(items, servers, times)
        return self.session.engine.costs

    def drain(self) -> CostBreakdown:
        """Flush the pending remainder (padded ragged chunk), block the
        ring, and sync state + costs into the wrapped numpy session."""
        t0 = _time.perf_counter()
        self._flush()
        with obs.span("live.sync", call="drain"):
            self._block()
            self._sync_state()
        self.session._wall += _time.perf_counter() - t0
        return self.session.engine.costs

    # -- snapshot / restore ------------------------------------------------
    def snapshot(self) -> dict:
        """Checkpoint pytree, bitwise-compatible with the ``CacheSession``
        path.  Completed chunks are synced into the session state; the
        un-dispatched pending buffer travels under ``snap["live"]`` (so
        the processed prefix stays chunk-aligned on resume — required
        for bitwise-identical continuation).  ``drain()`` first if the
        snapshot must be loadable by a plain ``CacheSession``."""
        with obs.span("live.sync", call="snapshot"):
            self._block()
            self._sync_state()
        snap = self.session.snapshot()
        items, servers, times = self._pend_concat()
        snap["live"] = {
            "pend_items": items.astype(np.int64),
            "pend_servers": servers.astype(np.int64),
            "pend_times": times.astype(np.float64),
        }
        return snap

    def restore(self, snap: dict) -> "LiveServingEngine":
        """Load a snapshot from either a live engine or a plain
        ``CacheSession``; resumes bit-identically.  Outstanding futures
        from before the restore are invalidated."""
        self._probes.clear()
        self._probe_ends.clear()
        self._ready_total = 0
        self._carry = None          # re-seed from the restored state
        self._cgm_carry = None
        self._ofs = []
        self._cgm_bound = False
        self._spec_j = None
        self._acc_dirty = False
        self.session.restore(snap)
        self._part = self.session.partition
        self._pend = []
        self._pend_n = 0
        self._submitted_total = 0
        self._dispatched_total = 0
        self._host_nreq = 0
        self._host_nitem = 0
        self._last_sub = self.session._last_t
        live = snap.get("live")
        if live is not None and live["pend_times"].shape[0]:
            items = np.asarray(live["pend_items"])
            servers = np.asarray(live["pend_servers"], np.int64)
            times = np.asarray(live["pend_times"], np.float64)
            self._pend = [(items, servers, times)]
            self._pend_n = times.shape[0]
            self._submitted_total = self._pend_n
            self._last_sub = float(times[-1])
        return self

    def result(self) -> RunResult:
        """Drain and return the run summary (``CacheSession.result``)."""
        self.drain()
        return self.session.result()

    # -- internals ---------------------------------------------------------
    def _pop_chunk(self, k: int):
        """Take exactly ``k`` requests off the pending buffer."""
        out_i, out_s, out_t = [], [], []
        need = k
        while need:
            it, sv, tm = self._pend[0]
            take = min(need, tm.shape[0])
            out_i.append(it[:take])
            out_s.append(sv[:take])
            out_t.append(tm[:take])
            if take == tm.shape[0]:
                self._pend.pop(0)
            else:
                self._pend[0] = (it[take:], sv[take:], tm[take:])
            need -= take
        self._pend_n -= k
        return (_cat_items(out_i), np.concatenate(out_s),
                np.concatenate(out_t))

    def _pend_concat(self):
        """Pending buffer as one array triple (without consuming it)."""
        if not self._pend:
            return (np.zeros((0, 1), np.int64), np.zeros(0, np.int64),
                    np.zeros(0, np.float64))
        return (_cat_items([p[0] for p in self._pend]),
                np.concatenate([p[1] for p in self._pend]),
                np.concatenate([p[2] for p in self._pend]))

    def _flush(self) -> None:
        if self._pend_n:
            n = self._pend_n
            self._dispatch(*self._pop_chunk(n))

    def _ensure_carry(self) -> None:
        if self._carry is not None:
            return
        eng = self.session.engine
        E0, a0 = ej.state_to_device(eng.state, self.n, self.layout)
        c = eng.costs
        # accumulator seeded with ABSOLUTE totals: syncs assign rather
        # than add, and resumes are exact (f64 roundtrips bitwise)
        acc0 = np.array([
            c.transfer, c.caching, c.keepalive_rent,
            float(c.n_misses), float(c.n_hits), float(c.items_transferred),
        ], np.float64)
        self._base_req = (c.n_requests, c.n_item_requests)
        self._host_nreq = 0
        self._host_nitem = 0
        with jax.enable_x64(True):
            E0, a0 = self.layout.place_state(E0, a0)
            self._carry = (
                jnp.asarray(E0, jnp.float64),
                jnp.asarray(a0, jnp.int32),
                jnp.asarray(acc0, jnp.float64),
            )
            self._spec_j = {
                k: jnp.asarray(v) for k, v in self._jeng._spec.items()}

    def _fix_dims(self, dims: dict) -> None:
        """Fix (or ratchet) the compiled chunk shape with headroom.

        Bucket-aware: the install axes (changed rows/items per boundary)
        scale with the catalog, so at bucketed 10^4-row layouts the
        ratchet steps grow with ``layout.state_rows`` — otherwise a big
        catalog would recompile dozens of times while the install width
        creeps up in 32-slot steps.  Dense small catalogs (rows <= 1024)
        keep the original step table bit-for-bit.
        """
        h = self.headroom
        rows = self.layout.state_rows(self.n)
        scale = max(1, rows // 1024)
        grown = {
            "nb": ej._bucket(int(dims["nb"] * 2), 4, 4),
            "ne": ej._bucket(int(dims["ne"] * h), 1024, 1024),
            "nu": ej._bucket(int(dims["nu"] * h), 512, 512),
            "na": ej._bucket(int(dims["na"] * h), 256, 256),
            "ncr": ej._bucket(int(dims["ncr"] * 2), 32 * scale, 32),
            "nci": ej._bucket(int(dims["nci"] * 2), 64 * scale, 64),
            "nmv": ej._bucket(int(dims["nmv"] * 2), 32 * scale, 32),
        }
        if self._dims is None:
            self._dims = grown
        else:
            self._dims = {k: max(self._dims[k], grown[k]) for k in grown}

    def _ensure_cgm_carry(self, sched) -> None:
        """Seed the CGM carry (once) with the compact dims of ``sched``."""
        if self._cgm_carry is not None:
            return
        from ..core.cgm_jax import (
            cgm_loop_statics, cgm_spec, init_cgm_carry, kernels_on_backend)

        eng = self.session.engine
        pol = self.policy
        uses_sizes = bool(eng.model.uses_sizes)
        item_sizes = eng.env.sizes() if uses_sizes else None
        carry0 = init_cgm_carry(
            eng.state, getattr(pol, "_prev_crm", None),
            self.session._window_arrays() if self.session._win else None,
            n=self.n, m=self.m, uses_sizes=uses_sizes,
            item_sizes=item_sizes, layout=self.layout, schedule=sched)
        c = eng.costs
        # absolute-total accumulator seed, as in _ensure_carry
        carry0["acc"] = np.array([
            c.transfer, c.caching, c.keepalive_rent,
            float(c.n_misses), float(c.n_hits), float(c.items_transferred),
        ], np.float64)
        self._base_req = (c.n_requests, c.n_item_requests)
        self._host_nreq = 0
        self._host_nitem = 0
        self._cgm_bound = False
        cfg = pol.config
        self._cgm_flags = (
            uses_sizes, bool(cfg.enable_split),
            bool(cfg.enable_approx_merge), bool(eng.seed_new_cliques),
            kernels_on_backend())
        cspec = cgm_spec(cfg, cfg.params, self.n)
        self._cgm_statics = cgm_loop_statics(
            cspec, carry0, enable_split=cfg.enable_split,
            enable_acm=cfg.enable_approx_merge)
        with jax.enable_x64(True):
            self._cgm_carry = {
                k: jnp.asarray(v) for k, v in carry0.items()}
            self._spec_j = {
                k: jnp.asarray(v) for k, v in self._jeng._spec.items()}
            self._cspec_j = {k: jnp.asarray(v) for k, v in cspec.items()}
            self._sz_j = (
                jnp.asarray(item_sizes, jnp.float64)
                if item_sizes is not None
                else jnp.ones(self.n, jnp.float64))

    def _grow_cgm_carry(self, h: int, wcap: int, dbuf: int) -> None:
        """Re-embed the carry into a larger compact workspace (ratchet).

        Blocks the ring (the donated carry must settle), zero-pads the
        previous-CRM workspace / -1-pads the window buffer, and ships
        the result back.  Costs one recompile, exactly like the generic
        path's dims ratchet."""
        self._block()
        c = {k: np.asarray(v) for k, v in self._cgm_carry.items()}
        oh = int(c["p_idx"].shape[0])
        ow, od = (int(x) for x in c["wbuf"].shape)
        h, wcap, dbuf = max(h, oh), max(wcap, ow), max(dbuf, od)
        if h > oh:
            p_idx = np.full(h, self.n, np.int32)
            p_idx[:oh] = c["p_idx"]
            c["p_idx"] = p_idx
            for k, dt in (("praw", np.float32), ("pnorm", np.float32),
                          ("pbin", bool)):
                a = np.zeros((h, h), dt)
                a[:oh, :oh] = c[k]
                c[k] = a
        if wcap > ow or dbuf > od:
            wbuf = np.full((wcap, dbuf), -1, np.int32)
            wbuf[:ow, :od] = c["wbuf"]
            c["wbuf"] = wbuf
        with jax.enable_x64(True):
            self._cgm_carry = {k: jnp.asarray(v) for k, v in c.items()}

    def _dispatch_cgm(self, items, servers, times) -> None:
        """Raw-tensor chunk dispatch: clique generation runs in-scan."""
        from ..core import cgm_jax

        sess = self.session
        eng = sess.engine
        R = times.shape[0]
        chunk = self._n["chunks"]
        with obs.span("live.pack", chunk=chunk, requests=R) as sp:
            if sess._next_cg is None:
                sess._next_cg = float(times[0]) + sess._t_cg
            # the open window's rows already live in the device buffer; the
            # chunk schedule's head-window capacity must account for them
            pre_rows = pre_slots = 0
            for w_it, _w_sv in sess._win:
                r = int(w_it.shape[0])
                wd = int(w_it.shape[1]) if w_it.ndim == 2 else 1
                pre_rows += r
                pre_slots += r * wd
            sched = cgm_jax.build_cgm_schedule(
                _Chunk(items, servers, times, self.n, self.m), sess._t_cg,
                uses_sizes=bool(eng.model.uses_sizes),
                next_cg0=sess._next_cg,
                hot_dims=cgm_jax.policy_hot_dims(self.policy),
                prefix_rows=pre_rows, prefix_slots=pre_slots)
            sp.set_metadata(windows=int(sched.boundary_steps.size))
            dims = ej.schedule_dims(sched)
            if self._cgm_dims is None or any(
                    dims[k] > self._cgm_dims[k] for k in dims):
                grown = {"nb": ej._bucket(int(dims["nb"] * 2), 4, 4),
                         "B": ej._bucket(int(dims["B"] * 2), 32, 32),
                         "d": dims["d"],
                         "h": min(self.n,
                                  ej._bucket(int(dims["h"] * 2), 32, 32)),
                         "W": ej._bucket(int(dims["W"] * 2), 64, 64)}
                self._cgm_dims = (grown if self._cgm_dims is None else {
                    k: max(self._cgm_dims[k], grown[k]) for k in grown})
            sched = ej.pad_schedule(sched, self._cgm_dims)
            # growing B re-derives wcap; fold it back into the ratchet
            self._cgm_dims["W"] = max(self._cgm_dims["W"], sched.wcap)
            # carry creation reads the PRE-chunk open window (sess._win)
            self._ensure_cgm_carry(sched)
            cw, cd = (int(x) for x in self._cgm_carry["wbuf"].shape)
            ch = int(self._cgm_carry["p_idx"].shape[0])
            if ch < sched.h or cw < sched.wcap or cd < sched.d:
                with obs.span("live.grow", chunk=chunk, requests=R):
                    self._grow_cgm_carry(sched.h, sched.wcap, sched.d)
                self._n["carry_grows"] += 1
            elif ch > sched.h:
                # a restored previous-window CRM bumped the carry's h past
                # the schedule's; ratchet the dims so they stay aligned
                self._cgm_dims["h"] = max(self._cgm_dims["h"], ch)
            if sched.next_cg is not None:
                sess._next_cg = sched.next_cg
            if sched.boundary_hit:
                sess._win = []
                self._cgm_bound = True
            if sched.win_start < R:
                sess._win.append((
                    np.array(items[sched.win_start:], dtype=np.int32,
                             copy=True),
                    np.array(servers[sched.win_start:], dtype=np.int32,
                             copy=True),
                ))
            sess._last_t = float(times[-1])
            self._host_nreq += sched.n_requests
            self._host_nitem += sched.n_item_requests
            self._dispatched_total += R
        xs_j = self._put(sched.xs, chunk, R)
        with obs.span("live.launch", chunk=chunk, requests=R):
            fn = _compiled_cgm_live_step(
                self._jeng._statics, eng.caching_charge, *self._cgm_flags,
                *self._cgm_statics)
            before = cgm_jax.SCAN_TRACES
            with jax.enable_x64(True):
                self._cgm_carry, (ofs, _) = fn(
                    self._spec_j, self._cspec_j, self._cgm_carry, xs_j,
                    self._sz_j)
            self.compiles += cgm_jax.SCAN_TRACES - before
        self._acc_dirty = True
        self._ofs.append((sched.boundary_steps, ofs))
        self._enqueue(ofs, chunk, R)

    def _dispatch(self, items, servers, times) -> None:
        """Pack one chunk's event tensors and launch it on the ring."""
        if self._cgm:
            self._dispatch_cgm(items, servers, times)
            return
        self._ensure_carry()
        sess = self.session
        eng = sess.engine
        R = times.shape[0]
        chunk = self._n["chunks"]
        windowed = sess._t_cg is not None
        with obs.span("live.pack", chunk=chunk, requests=R) as sp:
            if windowed and sess._next_cg is None:
                sess._next_cg = float(times[0]) + sess._t_cg
            sched = ej.build_schedule(
                self._part, _Chunk(items, servers, times),
                sess.policy.on_window if windowed else None,
                sess._t_cg,
                model=eng.model, env=eng.env,
                seed_new_cliques=eng.seed_new_cliques,
                next_cg0=sess._next_cg if windowed else None,
                win_prefix=(sess._window_arrays()
                            if windowed and sess._win else None),
                lookup=eng._lookup,
                layout=self.layout,
            )
            sp.set_metadata(windows=int(sched.xs["inst"].sum()))
            # T_CG window bookkeeping — identical to
            # CacheSession._feed_trace_jax
            if windowed:
                if sched.next_cg is not None:
                    sess._next_cg = sched.next_cg
                if sched.boundary_hit:
                    sess._win = []
                if sched.win_start < R:
                    sess._win.append((
                        np.array(items[sched.win_start:], dtype=np.int32,
                                 copy=True),
                        np.array(servers[sched.win_start:], dtype=np.int32,
                                 copy=True),
                    ))
            sess._last_t = float(times[-1])
            self._part = sched.final_partition
            self._host_nreq += sched.n_requests
            self._host_nitem += sched.n_item_requests
            self._dispatched_total += R
            dims = ej.schedule_dims(sched)
            if self._dims is None or any(
                    dims[k] > self._dims[k] for k in dims):
                self._fix_dims(dims)
            sched = ej.pad_schedule(sched, self._dims)
        xs_j = self._put(sched.xs, chunk, R)
        with obs.span("live.launch", chunk=chunk, requests=R):
            fn = _compiled_live_step(
                self._jeng._statics, eng.caching_charge, sched.const_dt)
            before = ej.SCAN_TRACES
            with jax.enable_x64(True):
                self._carry, probe = fn(self._spec_j, self._carry, xs_j)
            self.compiles += ej.SCAN_TRACES - before
        self._acc_dirty = True
        self._enqueue(probe, chunk, R)

    def _put(self, xs: dict, chunk: int, R: int) -> dict:
        """The chunk's event tensors, host -> device."""
        nbytes = sum(int(v.nbytes) for v in xs.values())
        with obs.span("live.put", chunk=chunk, requests=R, bytes=nbytes):
            with jax.enable_x64(True):
                xs_j = {key: jnp.asarray(v) for key, v in xs.items()}
        self._n["h2d_bytes"] += nbytes
        return xs_j

    def _enqueue(self, probe, chunk: int, R: int) -> None:
        """Put the chunk on the ring; past ``ring`` chunks in flight,
        block on the oldest (backpressure)."""
        self._probes.append(probe)
        self._probe_ends.append(self._dispatched_total)
        self._n["chunks"] += 1
        self._n["requests"] += R
        while len(self._probes) > self.ring:
            t0 = _time.perf_counter()
            with obs.span("live.ring_wait", chunk=chunk, requests=R,
                          waits_on=chunk + 1 - len(self._probes)):
                self._pop_ready()
            self._n["ring_waits"] += 1
            self._n["ring_wait_s"] += _time.perf_counter() - t0

    def _pop_ready(self) -> None:
        """Wait for the oldest in-flight chunk and take it off the ring."""
        self._probes.popleft().block_until_ready()
        self._ready_total = self._probe_ends.popleft()

    def _block(self) -> None:
        while self._probes:
            self._pop_ready()

    def _sync_costs(self) -> CostBreakdown:
        """Assign the device accumulator into the session's breakdown."""
        self._block()
        c = self.session.engine.costs
        acc_dev = (self._cgm_carry["acc"]
                   if self._cgm and self._cgm_carry is not None
                   else self._carry[2] if self._carry is not None else None)
        if acc_dev is not None and self._acc_dirty:
            acc = np.asarray(acc_dev)
            c.transfer = float(acc[0])
            c.caching = float(acc[1])
            c.keepalive_rent = float(acc[2])
            c.n_misses = int(acc[3])
            c.n_hits = int(acc[4])
            c.items_transferred = int(acc[5])
            c.n_requests = self._base_req[0] + self._host_nreq
            c.n_item_requests = self._base_req[1] + self._host_nitem
            self._acc_dirty = False
        return c

    def _sync_state(self) -> None:
        """Download the carry into the numpy engine (costs + cache state)."""
        self._sync_costs()
        if self._cgm:
            self._sync_state_cgm()
            return
        if self._carry is None:
            return
        eng = self.session.engine
        eng.state = CacheState.from_device(
            self._part, self._carry[0], self._carry[1], self.m)
        eng._set_partition_caches(self._part)
        keep_fn = getattr(self.policy, "item_keep", None)
        if keep_fn is not None:
            # boundary evictions already ran on device; align the numpy
            # engine's keep-or-not mask for any later host-side feed()
            eng.set_item_keep(keep_fn(), evict=False)

    def _sync_state_cgm(self) -> None:
        """CGM-mode sync: carry dict -> engine state + policy bookkeeping
        (``cgm_jax.sync_policy_from_run`` folded across buffered chunks)."""
        from ..core.cgm_jax import partition_from_of
        from ..core.crm import WindowCRM

        if self._cgm_carry is None:
            return
        eng = self.session.engine
        pol = self.policy
        part = self._part
        if self._cgm_bound:
            part = partition_from_of(
                self.n, np.asarray(self._cgm_carry["of"]))
        eng.state = CacheState.from_device(
            part, self._cgm_carry["E"], self._cgm_carry["anchor"], self.m)
        eng._set_partition_caches(part)
        nbd = 0
        for bsteps, ofs in self._ofs:
            if bsteps.size:
                ofs_np = np.asarray(ofs)
                for b in bsteps:
                    sizes = np.bincount(ofs_np[int(b)]).astype(np.int64)
                    pol.size_history.append(sizes[sizes > 1])
                nbd += int(bsteps.size)
        self._ofs = []
        pol.n_windows += nbd
        if self._cgm_bound:
            pol._partition = part
            pol._prev_crm = WindowCRM.from_compact(
                np.asarray(self._cgm_carry["p_idx"]),
                np.asarray(self._cgm_carry["praw"]),
                np.asarray(self._cgm_carry["pnorm"]),
                np.asarray(self._cgm_carry["pbin"]), n=self.n)
        self._part = part


def _cat_items(chunks: list) -> np.ndarray:
    """Concatenate (R_i, d_i) item arrays, -1-padding to the widest d."""
    if len(chunks) == 1:
        return chunks[0]
    d = max(a.shape[1] for a in chunks)
    R = sum(a.shape[0] for a in chunks)
    out = np.full((R, d), -1, dtype=np.int64)
    r = 0
    for a in chunks:
        out[r:r + a.shape[0], :a.shape[1]] = a
        r += a.shape[0]
    return out
