"""Device-resident JAX replay backend: one jit'd ``lax.scan`` per trace.

The NumPy engine (``core/engine.py``) replays a trace as a Python loop of
``handle_batch`` calls — vectorised inside a batch, but dispatching dozens
of NumPy ops per batch and re-deriving the same event structure every run.
This module splits the replay in two (DESIGN.md §10):

* **Host schedule** (``build_schedule``): everything that is a pure
  function of (trace, clique-generation) and NOT of cache state — the
  T_CG window walk, the policy's clique generation, the per-batch
  (request, clique) event construction of :func:`~repro.core.engine.batch_events`
  (dedup, sort orders, lags, segment flags) and the partition-install
  matching of :func:`~repro.core.engine.match_partitions` — is computed
  once on host and packed into fixed-shape, -padded event tensors.
  Reusing the NumPy engine's own construction helpers makes the schedule
  bit-identical to what ``handle_batch`` would have derived inline.

* **Device scan** (``_replay_impl``): the state recurrence — expiries
  ``E``, Alg.-6 ``anchor``, ratcheting, Alg.-5 cost accounting, and the
  partition-install state translation — runs as one ``jax.lax.scan`` over
  the schedule's batches inside a single ``jit``, with ``CacheState``
  living on device for the whole trace.  Under per-server dt the anchor
  resolution and the pair-expiry update are segmented running
  (arg)max doubling scans (``kernels/segment_reduce.py``) in jnp on
  every backend: they carry f64 expiries, which Mosaic refuses.

The state trajectory is float-for-float identical to the NumPy engine
(same f64 ops on the same operands); cost totals differ only by summation
order inside a batch, which is why parity holds at 1e-9 relative
(tests/test_sweep.py) on every chunking.

Everything runs under ``jax.enable_x64(True)`` so the engine's float64
semantics survive; the rest of the repo stays on default x32.

Because the schedule is state-free, ``core/sweep.py`` can share ONE
schedule across every scenario that prices the same (trace x clique-gen
hyperparameters) point and ``vmap`` the compiled replay over stacked
cost-model parameters and initial states — the grid sweep the paper's
Figs. 5-10 need.

State layout: by default the device ``E`` is ``(n + 1, m)`` — one row
per POSSIBLE clique id (a partition of n items has k <= n cliques) plus
a dump row that absorbs masked scatter writes and padding-event gathers;
the NumPy engine's ``(k, m)`` state is the live prefix ``E[:k]``.  The
geometry is owned by :class:`repro.core.state_layout.StateLayout`
(``layout=`` on every entry point): ``bucketed`` rounds the state dims
up to padding buckets so mixed-(n, m) sweeps compile per bucket cohort,
``row_sharded`` distributes the state rows over a mesh axis.  The dump
row is ALWAYS the last state row (``schedule.nrow - 1``); the scan body
derives it from the carry shape, so one compiled scan serves every
catalog sharing a bucket.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .cliques import CliquePartition
from .cost import (
    CacheEnvironment,
    CostBreakdown,
    CostModel,
    HeterogeneousCostModel,
    Table1CostModel,
    TieredCostModel,
)
from ..kernels.segment_reduce import (
    seg_running_argmax_jnp,
    seg_running_max_jnp,
)
from .engine import (
    CacheState,
    CachingCharge,
    ReplayEngine,
    batch_events,
    match_partitions,
    window_seed_servers,
)
from .state_layout import StateLayout

#: the in-checkout compile-cache directory used when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset (git-ignored)
REPO_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache")

_COMPILE_CACHE_SET = False


def enable_compile_cache() -> None:
    """Turn on XLA's persistent compilation cache (first device use).

    Every device path (replay, sweep, live) calls this before it
    compiles.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and this sets no directory; otherwise the cache goes to
    one fixed path inside the checkout (``REPO_COMPILE_CACHE``) — the
    path is part of the cache key, so it must not move between runs.
    Idempotent.
    """
    global _COMPILE_CACHE_SET
    if _COMPILE_CACHE_SET:
        return
    _COMPILE_CACHE_SET = True
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir",
                          os.path.normpath(REPO_COMPILE_CACHE))
        # a process's first compile decides once whether the cache is
        # used: make a compile that ran before this point check again
        compilation_cache.reset_cache()
    # the scan compiles in ~1s and serialises small; the defaults
    # (1s floor) would skip borderline cohorts on fast machines
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# cost spec: the three batched CostModel hooks as data + static kind
# ---------------------------------------------------------------------------
#: cost models the JAX backend can express as jnp hooks
JAX_COST_MODELS = ("table1", "tiered", "heterogeneous")


def cost_spec(model: CostModel, env: CacheEnvironment) -> tuple[dict, tuple]:
    """(spec arrays, static key) reproducing ``model``'s batched hooks.

    ``spec`` is a dict of numpy arrays (vmap-stackable per scenario);
    the static key ``(kind, literal, n_tiers)`` selects the jnp formula.
    """
    p = env.params
    m = env.m
    spec = {
        "dt": np.asarray(model.dt(), dtype=np.float64),
        "alpha": np.float64(p.alpha),
        "lam": np.float64(p.lam),
        "mu": np.float64(p.mu),
        "lam_j": env.lam_per_server(),
        "mu_j": env.mu_per_server(),
        "tier_lo": np.zeros(0),
        "tier_hi": np.zeros(0),
        "tier_rates": np.zeros(0),
    }
    literal = p.cost_mode == "paper_literal"
    if isinstance(model, TieredCostModel):
        spec["tier_lo"] = model._lo.astype(np.float64)
        spec["tier_hi"] = model._hi.astype(np.float64)
        spec["tier_rates"] = model.rates.astype(np.float64)
        return spec, ("tiered", literal, int(model.rates.shape[0]))
    if isinstance(model, HeterogeneousCostModel):
        return spec, ("heterogeneous", literal, 0)
    if isinstance(model, Table1CostModel):
        return spec, ("table1", literal, 0)
    raise NotImplementedError(
        f"cost model {model.name!r} has no JAX formula; the JAX backend "
        f"supports {JAX_COST_MODELS} — run it with the numpy engine")


def _transfer_hook(kind, spec, counts, sizes, j):
    if kind[0] == "table1":
        if kind[1]:  # paper_literal: Alg. 5 line 11 as written
            packed = spec["alpha"] * spec["mu"] * counts
        else:
            packed = (1.0 + (counts - 1.0) * spec["alpha"]) * spec["lam"]
        return jnp.where(counts > 1, packed, counts * spec["lam"])
    if kind[0] == "tiered":
        v = sizes[:, None]
        seg = jnp.clip(
            jnp.minimum(v, spec["tier_hi"]) - spec["tier_lo"], 0.0, None)
        return spec["lam_j"][j] * (seg * spec["tier_rates"]).sum(axis=-1)
    # heterogeneous
    disc = jnp.where(
        counts > 1, (1.0 + (counts - 1.0) * spec["alpha"]) / counts, 1.0)
    return spec["lam_j"][j] * sizes * disc


def _rate_hook(kind, spec, counts, sizes, j):
    if kind[0] == "table1":
        return counts * spec["mu"]
    return spec["mu_j"][j] * sizes


# ---------------------------------------------------------------------------
# the host-built replay schedule
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ReplaySchedule:
    """Fixed-shape padded event tensors of one trace replay (host numpy).

    ``xs[key]`` has leading axis nb (scan steps); event axis padded to
    ``ne``; install arrays padded to n rows (+ dump).  The same schedule
    serves every scenario sharing (trace, clique-gen hyperparameters) —
    see :mod:`repro.core.sweep`.
    """

    n: int
    m: int
    nb: int
    ne: int
    const_dt: bool
    uses_sizes: bool
    xs: dict
    n_requests: int
    n_item_requests: int
    partition0: CliquePartition
    final_partition: CliquePartition
    win_start: int              # open-window start index into the trace
    boundary_hit: bool          # did any Event-1 boundary fire in this trace
    next_cg: float | None       # T_CG boundary after the last request
    # state geometry the index fills were built for (StateLayout.state_dims;
    # dense default = (n + 1, m)); the dump row is always nrow - 1
    nrow: int = 0
    ncol: int = 0

    @property
    def state_rows(self) -> int:
        return self.nrow if self.nrow else self.n + 1

    @property
    def state_cols(self) -> int:
        return self.ncol if self.ncol else self.m


def _bucket(x: int, step: int, floor: int) -> int:
    """Round up to a multiple of ``step`` (>= floor) — shape buckets keep
    jit cache hits across schedules without pow2-level padding waste."""
    return max(floor, -(-x // step) * step)


#: target deduplicated events per scan step under default (event-balanced)
#: slicing: windows are split into equal-event batches instead of fixed
#: request counts, which keeps the padded (nb, ne) tensors dense
NE_TARGET = 8192


def _part_cost_arrays(part: CliquePartition, item_sizes: np.ndarray | None):
    """Per-clique member counts + total volumes (engine _set_partition_caches)."""
    sizes = part.sizes().astype(np.int64)
    if item_sizes is None or part.k == 0:
        return sizes, None
    order = part.member_order()
    starts = np.zeros(part.k, np.int64)
    np.cumsum(sizes[:-1], out=starts[1:])
    return sizes, np.add.reduceat(item_sizes[order], starts)


def build_schedule(
    partition0: CliquePartition,
    trace,
    clique_generator: Callable | None,
    t_cg: float | None,
    *,
    model: CostModel,
    env: CacheEnvironment,
    batch_size: int | None = None,
    seed_new_cliques: bool = True,
    next_cg0: float | None = None,
    win_prefix: tuple[np.ndarray, np.ndarray] | None = None,
    lookup: Callable | None = None,
    layout: StateLayout | str | None = None,
) -> ReplaySchedule:
    """Walk the trace exactly as ``ReplayEngine.replay`` does and emit the
    padded event tensors + install records of every batch.

    ``next_cg0``/``win_prefix`` support mid-stream continuation (a
    :class:`~repro.core.session.CacheSession` that already has an open
    T_CG window); fresh replays leave them None.
    """
    from .engine import DEFAULT_BATCH_SIZE, _numpy_clique_lookup

    n, m = env.n, env.m
    lay = StateLayout.resolve(layout)
    nrow, ncol = lay.state_dims(n, m)
    K = nrow - 1                                # dump row index (last row)
    bs = DEFAULT_BATCH_SIZE if batch_size is None else max(1, int(batch_size))
    lookup = lookup or _numpy_clique_lookup
    uses_sizes = bool(model.uses_sizes)
    item_sizes = env.sizes() if uses_sizes else None
    dt_arr = np.asarray(model.dt(), dtype=np.float64)
    const_dt = m == 0 or bool((dt_arr == dt_arr[0]).all())

    times, servers, items = trace.times, trace.servers, trace.items
    R = int(times.shape[0])
    cur = partition0
    sizes_c, csizes_c = _part_cost_arrays(cur, item_sizes)

    # keep-or-not (TTL) hook: a policy exposing ``item_keep()`` on the
    # generator's bound object ships a per-event nokeep mask plus boundary
    # eviction rows through the schedule — the device mirror of
    # ``ReplayEngine.set_item_keep`` (engine.py)
    keep_fn = None
    if clique_generator is not None:
        pol = getattr(clique_generator, "__self__", None)
        keep_fn = getattr(pol, "item_keep", None)

    def _clique_nk_of(part: CliquePartition, keep: np.ndarray) -> np.ndarray:
        """Clique-level nokeep mask: nokeep iff ANY member is nokeep."""
        if part.k == 0:
            return np.zeros(0, bool)
        psz = part.sizes().astype(np.int64)
        order = part.member_order()
        starts = np.zeros(part.k, np.int64)
        np.cumsum(psz[:-1], out=starts[1:])
        return np.add.reduceat((~keep)[order].astype(np.int64), starts) > 0

    cur_keep = (np.asarray(keep_fn(), bool).copy()
                if keep_fn is not None else None)
    cur_nk = _clique_nk_of(cur, cur_keep) if cur_keep is not None else None

    batches: list[dict] = []
    pending_install: dict | None = None
    n_requests = 0
    n_item_requests = 0

    def _emit(pos: int, stop: int) -> None:
        nonlocal pending_install, n_requests, n_item_requests
        ev = batch_events(
            cur.clique_of, cur.k, m,
            np.atleast_2d(items[pos:stop]), servers[pos:stop],
            times[pos:stop], lookup,
            item_sizes if csizes_c is not None else None,
        )
        n_requests += stop - pos
        n_item_requests += ev.n_valid
        size_e = sizes_c[ev.ev_c].astype(np.float64)
        csize_e = (csizes_c[ev.ev_c] if csizes_c is not None else size_e)
        n_req = ev.n_req.astype(np.float64)
        req_size = (ev.req_size if ev.req_size is not None else n_req)
        rec = {
            "ev": ev, "size": size_e, "csize": csize_e,
            "n_req": n_req, "req_size": np.asarray(req_size, np.float64),
            "install": pending_install,
        }
        if cur_nk is not None:
            rec["nk"] = (cur_nk[ev.ev_c] if ev.n_events
                         else np.zeros(0, bool))
        pending_install = None
        batches.append(rec)

    def _record_install(part: CliquePartition, now: float,
                        w_it: np.ndarray, w_sv: np.ndarray) -> None:
        nonlocal pending_install, cur, sizes_c, csizes_c, cur_keep, cur_nk
        if pending_install is not None:     # two Event-1s with no requests
            _emit(0, 0)                     # between them: flush on an
            # empty batch so installs stay one-per-scan-step
        matched, cand = match_partitions(cur, part)
        k = part.k
        new_sizes = part.sizes().astype(np.int64)
        # COMPACT translation: only CHANGED cliques need the member-wise
        # segment-min / seeding — matched rows are a plain row gather via
        # ``cand``.  Windows drift slowly, so the device install touches
        # O(changed x m), not O(n x m).
        chg = np.nonzero(~matched)[0]
        order = part.member_order()
        starts = np.zeros(k, np.int64)
        np.cumsum(new_sizes[:-1], out=starts[1:])
        chg_item = (
            np.concatenate(
                [order[starts[c]: starts[c] + new_sizes[c]] for c in chg])
            if chg.size else np.zeros(0, np.int64))
        chg_seg = np.repeat(np.arange(chg.size), new_sizes[chg])
        seed_j = np.zeros(chg.size, np.int32)
        seed_ok = np.zeros(chg.size, bool)
        if seed_new_cliques and w_it is not None and k > 0 and chg.size:
            js = window_seed_servers(m, part, w_it, w_sv)
            seed_j = js[chg].astype(np.int32)
            seed_ok = new_sizes[chg] > 1
            if cur_keep is not None:
                # OLD-mask guard (engine install_partition): never seed a
                # clique holding a keep-or-not evicted item
                has_nk = np.bincount(
                    chg_seg,
                    weights=(~cur_keep)[chg_item].astype(np.float64),
                    minlength=chg.size) > 0
                seed_ok &= ~has_nk
        # matched cliques that KEPT their index need no write at all — in
        # the steady state (partition drifting slowly) the whole install
        # reduces to a handful of row scatters
        mov = np.nonzero(matched & (cand != np.arange(k)))[0]
        chg_ok = np.ones(chg.size, bool)
        if keep_fn is not None:
            # NEW-mask boundary eviction (engine set_item_keep): cliques
            # holding an item that just flipped keep->nokeep drop their
            # copies.  Rows already in chg flip ok=False (the install step
            # turns ok=False rows into E=0 / anchor=-1); other evicted
            # rows join chg as member-less ok=False rows; moved copies of
            # evicted cliques are dropped from the row-move list.
            new_keep = np.asarray(keep_fn(), bool).copy()
            newly_nk = cur_keep & ~new_keep
            if newly_nk.any():
                ev_rows = np.unique(
                    part.clique_of[np.nonzero(newly_nk)[0]]).astype(np.int64)
                evict = np.zeros(k, bool)
                evict[ev_rows] = True
                chg_ok[evict[chg]] = False
                mov = mov[~evict[mov]]
                extra = ev_rows[~np.isin(ev_rows, chg)]
                chg = np.concatenate([chg, extra])
                chg_ok = np.concatenate(
                    [chg_ok, np.zeros(extra.size, bool)])
                seed_j = np.concatenate(
                    [seed_j, np.zeros(extra.size, np.int32)])
                seed_ok = np.concatenate(
                    [seed_ok, np.zeros(extra.size, bool)])
            cur_keep = new_keep
            cur_nk = _clique_nk_of(part, new_keep)
        pending_install = {
            "now": np.float64(now),
            "mov_dst": mov.astype(np.int32),
            "mov_src": cand[mov].astype(np.int32),
            "chg_rows": chg.astype(np.int32),
            "chg_ok": chg_ok,
            "chg_src": cur.clique_of[chg_item].astype(np.int32),
            "chg_seg": chg_seg.astype(np.int32),
            "seed_j": seed_j,
            "seed_ok": seed_ok,
        }
        cur = part
        sizes_c, csizes_c = _part_cost_arrays(cur, item_sizes)

    # -- the T_CG boundary walk (mirrors ReplayEngine.replay) --------------
    use_cg = clique_generator is not None and t_cg is not None
    balanced = batch_size is None      # event-balanced default slicing
    if balanced and R > 0:
        cum = np.zeros(R + 1, np.int64)
        np.cumsum((items >= 0).sum(axis=1), out=cum[1:])
    if R > 0:
        if next_cg0 is not None:
            next_cg = float(next_cg0)
        else:
            next_cg = float(times[0]) + t_cg if t_cg is not None else np.inf
    else:
        next_cg = next_cg0 if next_cg0 is not None else np.inf
    win_start = 0
    boundary_hit = False
    pos = 0
    while pos < R:
        cut = R
        if use_cg:
            cut = int(np.searchsorted(times, next_cg, side="left"))
            if cut <= pos:
                t = float(times[pos])
                w_it = items[win_start:pos]
                w_sv = servers[win_start:pos]
                if win_prefix is not None:
                    p_it, p_sv = win_prefix
                    if p_it.shape[0]:
                        d = max(int(p_it.shape[1]), int(w_it.shape[1]))
                        full = np.full(
                            (p_it.shape[0] + w_it.shape[0], d), -1, np.int64)
                        full[: p_it.shape[0], : p_it.shape[1]] = p_it
                        if w_it.shape[0]:
                            full[p_it.shape[0]:, : w_it.shape[1]] = w_it
                        w_it = full
                        w_sv = np.concatenate(
                            [np.asarray(p_sv, np.int64),
                             np.asarray(w_sv, np.int64)])
                    win_prefix = None
                part = clique_generator(w_it, w_sv, t)
                if part is not None:
                    _record_install(part, t, w_it, w_sv)
                elif keep_fn is not None and not np.array_equal(
                        cur_keep, np.asarray(keep_fn(), bool)):
                    # mask moved without a new partition: identity install
                    # record carrying only the boundary evictions
                    _record_install(cur, t, w_it, w_sv)
                win_start = pos
                boundary_hit = True
                while next_cg <= t:
                    next_cg += t_cg
                continue
        if balanced:
            # split [pos, cut) into equal-EVENT batches (any chunking
            # reproduces the costs at 1e-9 — the PR-2 invariant — so the
            # device schedule is free to pick dense slices)
            est = int(cum[cut] - cum[pos])
            nbat = max(1, -(-est // NE_TARGET))
            prev = pos
            for kb in range(1, nbat + 1):
                if kb == nbat:
                    stop = cut
                else:
                    target = cum[pos] + (est * kb) // nbat
                    stop = int(np.searchsorted(cum, target, side="left"))
                    stop = min(max(stop, prev + 1), cut)
                if stop > prev:
                    _emit(prev, stop)
                    prev = stop
            pos = cut
        else:
            stop = min(pos + bs, cut)
            _emit(pos, stop)
            pos = stop
    if pending_install is not None:         # trailing Event 1, no requests
        _emit(0, 0)

    # -- stack + pad into fixed-shape tensors -------------------------------
    # nu / na: compacted per-step state-update widths — scatters touch only
    # the segment-last events ((c,j) pairs / cliques), not the full event
    # axis, which is what keeps XLA's serialized CPU scatters off the
    # critical path
    nb_raw = len(batches)
    nb = _bucket(nb_raw, 4, 4)
    ne = _bucket(max((r["ev"].n_events for r in batches), default=1), 256, 64)
    nu = _bucket(
        max((int(r["ev"].last_cj_s.sum()) for r in batches), default=1),
        128, 32)
    na = _bucket(
        max((int(r["ev"].last_c_s.sum()) for r in batches), default=1),
        32, 32)
    installs = [r["install"] for r in batches if r["install"] is not None]
    # +1 slack: the last compact row/segment is always padding, so padded
    # items can never corrupt a real segment's min
    ncr = _bucket(
        max((i["chg_rows"].size for i in installs), default=0) + 1, 8, 8)
    nci = _bucket(
        max((i["chg_src"].size for i in installs), default=0) + 1, 16, 16)
    nmv = _bucket(
        max((i["mov_dst"].size for i in installs), default=0), 8, 8)

    def zeros(dtype, *shape):
        return np.zeros((nb, *shape), dtype)

    xs = {
        "ev_c": np.full((nb, ne), K, np.int32),
        "ev_j": zeros(np.int32, ne),
        "ev_t": zeros(np.float64, ne),
        "n_req": zeros(np.float64, ne),
        "size": zeros(np.float64, ne),
        "val": zeros(bool, ne),
        "first_cj": zeros(bool, ne),
        "prev_cj_t": zeros(np.float64, ne),
        # compacted (c, j) expiry writes + per-clique anchor writes
        "upd_c": np.full((nb, nu), K, np.int32),
        "upd_j": zeros(np.int32, nu),
        "anc_c": np.full((nb, na), K, np.int32),
        "inst": zeros(bool),
        "inst_now": zeros(np.float64),
        "inst_mov_dst": np.full((nb, nmv), K, np.int32),
        "inst_mov_src": np.full((nb, nmv), K, np.int32),
        "inst_chg_rows": np.full((nb, ncr), K, np.int32),
        "inst_chg_ok": zeros(bool, ncr),
        "inst_seed_j": zeros(np.int32, ncr),
        "inst_seed_ok": zeros(bool, ncr),
        "inst_chg_src": zeros(np.int32, nci),
        "inst_chg_seg": np.full((nb, nci), ncr - 1, np.int32),
    }
    if keep_fn is not None:
        # presence keyed on the HOOK, not the mask content: an all-keep
        # window still ships the (all-False) tensor so every chunk of a
        # stream shares one input structure (and one compile)
        xs["nokeep"] = zeros(bool, ne)
    if uses_sizes:
        # count-based models (table1) read size/n_req twice instead of
        # shipping duplicate volume tensors through the scan
        xs["csize"] = zeros(np.float64, ne)
        xs["req_size"] = zeros(np.float64, ne)
    if const_dt:
        xs.update(
            first_c=zeros(bool, ne),
            prev_j=np.full((nb, ne), -1, np.int32),
            upd_t=zeros(np.float64, nu),
            anc_j=zeros(np.int32, na),
            anc_t=zeros(np.float64, na),
        )
    else:
        xs.update(
            inv_o_c=zeros(np.int32, ne),
            c_s=np.full((nb, ne), K, np.int32),
            j_s=zeros(np.int32, ne),
            t_s=zeros(np.float64, ne),
            first_cs=np.ones((nb, ne), bool),
            cj_j_s=zeros(np.int32, ne),
            cj_t_s=zeros(np.float64, ne),
            first_cjs=np.ones((nb, ne), bool),
            pos_u=zeros(np.int32, nu),
            pos_a=zeros(np.int32, na),
        )

    for b, rec in enumerate(batches):
        ev = rec["ev"]
        e = ev.n_events
        if e:
            xs["ev_c"][b, :e] = ev.ev_c
            xs["ev_j"][b, :e] = ev.ev_j
            xs["ev_t"][b, :e] = ev.ev_t
            xs["n_req"][b, :e] = rec["n_req"]
            xs["size"][b, :e] = rec["size"]
            if uses_sizes:
                xs["req_size"][b, :e] = rec["req_size"]
                xs["csize"][b, :e] = rec["csize"]
            xs["val"][b, :e] = True
            xs["first_cj"][b, :e] = ev.first_cj
            xs["prev_cj_t"][b, :e] = ev.prev_cj_t
            li = ev.o_cj[ev.last_cj_s]          # one event per (c, j) pair
            lc = ev.o_c[ev.last_c_s]            # one event per clique
            nk_e = rec.get("nk")
            if nk_e is not None:
                xs["nokeep"][b, :e] = nk_e
                # nokeep cliques never store state: route their compacted
                # expiry/anchor writes to the dump row
                xs["upd_c"][b, : li.size] = np.where(
                    nk_e[li], K, ev.ev_c[li])
                xs["anc_c"][b, : lc.size] = np.where(
                    nk_e[lc], K, ev.ev_c[lc])
            else:
                xs["upd_c"][b, : li.size] = ev.ev_c[li]
                xs["anc_c"][b, : lc.size] = ev.ev_c[lc]
            xs["upd_j"][b, : li.size] = ev.ev_j[li]
            if const_dt:
                xs["first_c"][b, :e] = ev.first_c
                xs["prev_j"][b, :e] = ev.prev_j
                xs["upd_t"][b, : li.size] = ev.ev_t[li]
                xs["anc_j"][b, : lc.size] = ev.ev_j[lc]
                xs["anc_t"][b, : lc.size] = ev.ev_t[lc]
            else:
                inv = np.empty(e, np.int32)
                inv[ev.o_c] = np.arange(e, dtype=np.int32)
                xs["inv_o_c"][b, :e] = inv
                xs["c_s"][b, :e] = ev.cs
                xs["j_s"][b, :e] = ev.ev_j[ev.o_c]
                xs["t_s"][b, :e] = ev.ev_t[ev.o_c]
                xs["first_cs"][b, :e] = ev.first_c_s
                xs["cj_j_s"][b, :e] = ev.ev_j[ev.o_cj]
                xs["cj_t_s"][b, :e] = ev.ev_t[ev.o_cj]
                xs["first_cjs"][b, :e] = ev.first_cj_s
                xs["pos_u"][b, : li.size] = np.nonzero(ev.last_cj_s)[0]
                xs["pos_a"][b, : lc.size] = np.nonzero(ev.last_c_s)[0]
        inst = rec["install"]
        if inst is not None:
            nr = inst["chg_rows"].size
            ni = inst["chg_src"].size
            nv = inst["mov_dst"].size
            xs["inst"][b] = True
            xs["inst_now"][b] = inst["now"]
            xs["inst_mov_dst"][b, :nv] = inst["mov_dst"]
            xs["inst_mov_src"][b, :nv] = inst["mov_src"]
            xs["inst_chg_rows"][b, :nr] = inst["chg_rows"]
            xs["inst_chg_ok"][b, :nr] = inst["chg_ok"]
            xs["inst_seed_j"][b, :nr] = inst["seed_j"]
            xs["inst_seed_ok"][b, :nr] = inst["seed_ok"]
            xs["inst_chg_src"][b, :ni] = inst["chg_src"]
            xs["inst_chg_seg"][b, :ni] = inst["chg_seg"]

    return ReplaySchedule(
        n=n, m=m, nb=nb, ne=ne, const_dt=const_dt, uses_sizes=uses_sizes,
        xs=xs, n_requests=n_requests, n_item_requests=n_item_requests,
        partition0=partition0, final_partition=cur,
        win_start=win_start, boundary_hit=boundary_hit,
        next_cg=None if not use_cg or R == 0 else float(next_cg),
        nrow=nrow, ncol=ncol,
    )


def schedule_dims(s) -> dict:
    """The padded axis sizes of a schedule (for cross-schedule alignment).

    Accepts either a generic :class:`ReplaySchedule` or a CGM schedule
    (``core.cgm_jax.CGMSchedule``, duck-typed on ``boundary_steps``) so
    streamed sessions can ratchet both kinds through one dims dict.
    """
    if hasattr(s, "boundary_steps"):
        return {"nb": s.nb, "B": s.B, "d": s.d, "h": s.h, "W": s.wcap}
    d = {"nb": s.nb, "ne": s.ne,
         "nu": s.xs["upd_c"].shape[1], "na": s.xs["anc_c"].shape[1],
         "ncr": s.xs["inst_chg_rows"].shape[1],
         "nci": s.xs["inst_chg_src"].shape[1],
         "nmv": s.xs["inst_mov_dst"].shape[1]}
    return d


def pad_schedule(s, dims: dict):
    """Pad a schedule's tensors up to ``dims`` (a superset of its own).

    SweepEngine aligns every schedule of one sweep call to common shapes so
    the device scan compiles exactly ONCE per (n, m, path) — padded steps
    and slots are inert by the same masking rules as intra-schedule
    padding.  CGM schedules delegate to ``cgm_jax.pad_cgm_schedule``.
    """
    if hasattr(s, "boundary_steps"):
        from .cgm_jax import pad_cgm_schedule

        return pad_cgm_schedule(s, dims)
    mine = schedule_dims(s)
    if mine == dims:
        return s
    K = s.state_rows - 1
    old_ncr = mine["ncr"]
    fills = {
        "ev_c": K, "upd_c": K, "anc_c": K, "c_s": K,
        "inst_mov_dst": K, "inst_mov_src": K, "inst_chg_rows": K,
        "first_cs": True, "first_cjs": True,
        "prev_j": -1,
        "inst_chg_seg": dims["ncr"] - 1,
    }
    axis_of = {
        "upd_c": "nu", "upd_j": "nu", "upd_t": "nu", "pos_u": "nu",
        "anc_c": "na", "anc_j": "na", "anc_t": "na", "pos_a": "na",
        "inst_chg_rows": "ncr", "inst_chg_ok": "ncr",
        "inst_seed_j": "ncr", "inst_seed_ok": "ncr",
        "inst_mov_dst": "nmv", "inst_mov_src": "nmv",
        "inst_chg_src": "nci", "inst_chg_seg": "nci",
    }
    xs = {}
    for key, a in s.xs.items():
        # real segment ids never collide with the pad sentinel (values
        # <= ncr-2 by the +1 slack), so remapping it is unambiguous
        if key == "inst_chg_seg":
            a = np.where(a == old_ncr - 1, dims["ncr"] - 1, a)
        want = [dims["nb"]]
        if a.ndim == 2:
            want.append(dims[axis_of.get(key, "ne")])
        if list(a.shape) != want:
            out = np.full(want, fills.get(key, 0), a.dtype)
            out[tuple(slice(0, d) for d in a.shape)] = a
            a = out
        xs[key] = a
    return dataclasses.replace(s, nb=dims["nb"], ne=dims["ne"], xs=xs)


# ---------------------------------------------------------------------------
# the device scan
# ---------------------------------------------------------------------------
#: accumulator slots: transfer, caching, keepalive_rent, n_misses, n_hits,
#: items_transferred
N_ACC = 6


def _install_step(E, anchor, x, dt):
    """Partition-install state translation (install_partition on device).

    The translation is a sparse IN-PLACE delta: matched cliques that kept
    their index are untouched; matched cliques whose index moved are a
    compact row move (``inst_mov_*``); only the CHANGED cliques
    (``inst_chg_*``) pay the member-wise segment-min + Alg.-1 seeding.
    All value gathers read the PRE-install state (functional semantics:
    gathers materialize before the scatters).  The dump row K is rewritten
    by the compact padding (rows -> K, ok=False -> zeros/-1), so
    inter-install scatter garbage never accumulates.
    """
    ncr = x["inst_chg_rows"].shape[0]
    movE = E[x["inst_mov_src"]]                     # (nmv, m)
    movA = anchor[x["inst_mov_src"]]
    item_E = E[x["inst_chg_src"]]                   # (nci, m)
    min_E = jax.ops.segment_min(
        item_E, x["inst_chg_seg"], num_segments=ncr)
    now = x["inst_now"]
    ok = x["inst_chg_ok"]
    fresh = jnp.where(ok[:, None] & (min_E > now), min_E, 0.0)
    row_max = fresh.max(axis=1)
    anew = jnp.where(
        row_max > 0.0, jnp.argmax(fresh, axis=1).astype(jnp.int32), -1)
    need = ok & (row_max <= 0.0) & x["inst_seed_ok"]
    sj = x["inst_seed_j"]
    col = jax.lax.broadcasted_iota(jnp.int32, fresh.shape, 1)
    fresh = jnp.where(
        need[:, None] & (col == sj[:, None]), now + dt[sj][:, None], fresh)
    anew = jnp.where(need, sj, anew)
    E = E.at[x["inst_mov_dst"]].set(movE)
    anchor = anchor.at[x["inst_mov_dst"]].set(movA)
    E = E.at[x["inst_chg_rows"]].set(fresh)
    anchor = anchor.at[x["inst_chg_rows"]].set(anew)
    return E, anchor


#: number of times the scan body has been TRACED.  jax re-traces (and XLA
#: recompiles) once per new input structure, so the delta of this counter
#: across a run counts fresh compiles — tests assert chunked/streamed
#: replays reuse ONE compiled scan (tests/test_serving_live.py)
SCAN_TRACES = 0


def _replay_impl(spec, init, xs, *, kind, charge, const_dt):
    """scan body closure; (spec, init) may carry a vmapped scenario axis."""
    global SCAN_TRACES
    SCAN_TRACES += 1
    dt = spec["dt"]

    def step(carry, x):
        E, anchor, acc = carry
        # lax.cond, not where: the predicate comes from the UNBATCHED xs
        # (shared across vmap lanes), so non-install steps skip the
        # delta-translation entirely
        with jax.named_scope("install"):
            E, anchor = jax.lax.cond(
                x["inst"],
                lambda Ea: _install_step(Ea[0], Ea[1], x, dt),
                lambda Ea: Ea,
                (E, anchor),
            )
        with jax.named_scope("event_step"):
            return event_step(E, anchor, acc, x), None

    def event_step(E, anchor, acc, x):
        K = E.shape[0] - 1
        cl, j, t, val = x["ev_c"], x["ev_j"], x["ev_t"], x["val"]
        dt_e = dt[0] if const_dt else dt[j]
        E_before = jnp.where(
            x["first_cj"], E[cl, j], x["prev_cj_t"] + dt_e)
        # a zero that DEPENDS on every E gather of this step: added to the
        # expiry-scatter values below, it forces XLA to order the reads
        # before the write, which lets the scatter update the scan carry
        # IN PLACE instead of copying the whole state every step
        dep = 0.0 * E_before[0]

        # --- anchor resolution ----------------------------------------
        if const_dt:
            a0 = anchor[cl]
            anchor_alive = jnp.where(
                x["first_c"], (a0 == j) & (E_before > 0.0),
                x["prev_j"] == j)
        else:
            e_val_s = x["t_s"] + dt[x["j_s"]]
            v, bidx = seg_running_argmax_jnp(e_val_s, x["first_cs"])
            a0_s = anchor[x["c_s"]]
            Eg = E[x["c_s"], jnp.maximum(a0_s, 0)]     # finite gather
            dep = dep + 0.0 * Eg[0]
            Ea0_s = jnp.where(a0_s >= 0, Eg, -jnp.inf)
            prev_v = jnp.where(
                x["first_cs"], -jnp.inf,
                jnp.concatenate([jnp.full(1, -jnp.inf, v.dtype), v[:-1]]))
            prev_b = jnp.where(
                x["first_cs"], 0,
                jnp.concatenate([jnp.zeros(1, bidx.dtype), bidx[:-1]]))
            inbatch = (~x["first_cs"]) & (prev_v >= Ea0_s)
            anchor_seen_s = jnp.where(
                inbatch, x["j_s"][prev_b], a0_s).astype(jnp.int32)
            anchor_seen = anchor_seen_s[x["inv_o_c"]]   # un-sort by gather
            anchor_alive = (anchor_seen == j) & (E_before > 0.0)

        fresh = E_before > t
        if "nokeep" in x:
            # keep-or-not (TTL) cliques: forced miss — their state writes
            # are routed to the dump row, so lag chains must not
            # fabricate hits from them (mirrors engine.handle_batch)
            fresh = fresh & ~x["nokeep"]
            anchor_alive = anchor_alive & ~x["nokeep"]
        alive = fresh | anchor_alive
        miss = (~alive) & val
        lapsed = alive & (~fresh) & val

        # Alg. 6 ratcheting of lapsed anchor copies
        steps = jnp.ceil((t - E_before) / dt_e)
        r = E_before + steps * dt_e
        r = jnp.where(r <= t, r + dt_e, r)
        e_eff = jnp.where(fresh, E_before, jnp.where(lapsed, r, t))

        # --- costs (vectorized CostModel hooks) -----------------------
        size = x["size"]
        csize = x["csize"] if "csize" in x else size
        rate_stored = _rate_hook(kind, spec, size, csize, j)
        rent = jnp.where(lapsed, rate_stored * (e_eff - E_before), 0.0)
        tc = jnp.where(
            miss, _transfer_hook(kind, spec, size, csize, j), 0.0)
        if charge == "requested":
            rate = _rate_hook(
                kind, spec, x["n_req"],
                x["req_size"] if "req_size" in x else x["n_req"], j)
        else:
            rate = rate_stored
        dur = jnp.maximum((t + dt_e) - jnp.maximum(e_eff, t), 0.0)
        cval = (val & ~x["nokeep"]) if "nokeep" in x else val
        cc = jnp.where(cval, rate * dur, 0.0)

        nm = miss.sum()
        acc = acc + jnp.stack([
            tc.sum(), cc.sum(), rent.sum(),
            nm.astype(acc.dtype), (val.sum() - nm).astype(acc.dtype),
            jnp.where(miss, size, 0.0).sum(),
        ])

        # --- state update on the COMPACTED segment-last arrays --------
        uc, uj, ac = x["upd_c"], x["upd_j"], x["anc_c"]
        if const_dt:
            E = E.at[uc, uj].set(x["upd_t"] + dt[0] + dep)
            a_cur = anchor[ac]
            aE = E[ac, jnp.maximum(a_cur, 0)]        # POST-update E
            upd = (a_cur < 0) | (x["anc_t"] + dt[0] >= aE)
            anchor = anchor.at[jnp.where(upd, ac, K)].set(x["anc_j"])
        else:
            e_cj_s = x["cj_t_s"] + dt[x["cj_j_s"]]
            vmax = seg_running_max_jnp(e_cj_s, x["first_cjs"])
            E = E.at[uc, uj].set(vmax[x["pos_u"]] + dep)
            pa = x["pos_a"]
            win = v[pa] >= Ea0_s[pa]
            final_anchor = jnp.where(
                win, x["j_s"][bidx[pa]], a0_s[pa]).astype(jnp.int32)
            anchor = anchor.at[ac].set(final_anchor)
        return E, anchor, acc

    return jax.lax.scan(step, init, xs)[0]


@functools.lru_cache(maxsize=64)
def _compiled_replay(kind, charge, const_dt, vmapped):
    f = functools.partial(
        _replay_impl, kind=kind, charge=charge, const_dt=const_dt)
    if vmapped == "xs":       # trace-shard axis: a schedule PER lane
        f = jax.vmap(f, in_axes=(0, 0, 0))
    elif vmapped:             # scenario axis: one schedule, many specs
        f = jax.vmap(f, in_axes=(0, 0, None))
    return jax.jit(f)


def run_schedule(
    schedule: ReplaySchedule,
    spec: dict,
    statics: tuple,
    E0: np.ndarray,
    anchor0: np.ndarray,
    *,
    charge: CachingCharge = "requested",
    block: bool = True,
    layout: StateLayout | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Execute one schedule for one scenario; returns (E, anchor, acc).

    ``spec``/``E0``/``anchor0`` may carry a leading scenario axis (then all
    three outputs do too and the compiled replay is vmapped over it with
    the schedule shared unbatched across scenarios).  ``block=False``
    returns the device arrays without waiting — XLA keeps computing in the
    background while the caller builds the next group's schedule (the
    SweepEngine pipeline); materialize with ``np.asarray`` when needed.
    A row-sharded ``layout`` commits the state rows to its mesh placement
    before the scan, so GSPMD partitions the row gathers/scatters.
    """
    enable_compile_cache()
    vmapped = E0.ndim == 3
    fn = _compiled_replay(statics, charge, schedule.const_dt, vmapped)
    with jax.enable_x64(True):
        acc_shape = (E0.shape[0], N_ACC) if vmapped else (N_ACC,)
        if layout is not None and isinstance(E0, np.ndarray):
            # host inputs get the layout's mesh placement here; arrays a
            # caller (SweepEngine._shard) already committed keep theirs
            E0, anchor0 = layout.place_state(E0, anchor0)
        init = (
            jnp.asarray(E0, jnp.float64),
            jnp.asarray(anchor0, jnp.int32),
            jnp.zeros(acc_shape, jnp.float64),
        )
        spec_j = {k: jnp.asarray(v) for k, v in spec.items()}
        xs_j = {k: jnp.asarray(v) for k, v in schedule.xs.items()}
        E, anchor, acc = fn(spec_j, init, xs_j)
        if not block:
            return E, anchor, acc
        return np.asarray(E), np.asarray(anchor), np.asarray(acc)


def run_schedules(
    schedules: list,
    spec: dict,
    statics: tuple,
    E0: np.ndarray,
    anchor0: np.ndarray,
    *,
    charge: CachingCharge = "requested",
    block: bool = True,
    layout: StateLayout | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Execute S schedules lane-for-lane: lane i replays ``schedules[i]``
    under spec lane i — the trace-shard axis of :mod:`repro.core.sweep`.

    Unlike :func:`run_schedule` (one schedule shared unbatched across
    scenario lanes), the event tensors are STACKED along the lane axis and
    the compiled scan is vmapped over them too (``in_axes=(0, 0, 0)``).
    All schedules must share padded dims (``pad_schedule``) and
    (n, m, const_dt); ``spec``/``E0``/``anchor0`` carry the leading S axis.
    """
    enable_compile_cache()
    s0 = schedules[0]
    assert E0.ndim == 3 and E0.shape[0] == len(schedules)
    assert all(s.const_dt == s0.const_dt and schedule_dims(s) ==
               schedule_dims(s0) for s in schedules[1:])
    fn = _compiled_replay(statics, charge, s0.const_dt, "xs")
    with jax.enable_x64(True):
        if layout is not None and isinstance(E0, np.ndarray):
            E0, anchor0 = layout.place_state(E0, anchor0)
        init = (
            jnp.asarray(E0, jnp.float64),
            jnp.asarray(anchor0, jnp.int32),
            jnp.zeros((E0.shape[0], N_ACC), jnp.float64),
        )
        spec_j = {k: jnp.asarray(v) for k, v in spec.items()}
        # lanes replaying one schedule copy its event tensors once; the
        # lane axis is stacked on the device
        dev = {}
        for s in schedules:
            if id(s) not in dev:
                dev[id(s)] = {k: jnp.asarray(v) for k, v in s.xs.items()}
        xs_j = {k: jnp.stack([dev[id(s)][k] for s in schedules])
                for k in s0.xs}
        E, anchor, acc = fn(spec_j, init, xs_j)
        if not block:
            return E, anchor, acc
        return np.asarray(E), np.asarray(anchor), np.asarray(acc)


def fresh_state_arrays(
    n: int, m: int, layout: StateLayout | str | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Device-layout expiries + anchors, all empty (dense: (n+1, m))."""
    rows, cols = StateLayout.resolve(layout).state_dims(n, m)
    return (np.zeros((rows, cols), np.float64), np.full(rows, -1, np.int32))


def fresh_state_lanes(lanes: int | None, rows: int, cols: int,
                      shardings=None) -> tuple[jax.Array, jax.Array]:
    """:func:`fresh_state_arrays` of ``rows`` x ``cols`` made on the
    device, with a leading axis of ``lanes`` vmap lanes (``None``: none).
    ``shardings`` places (E, anchor) where given.  Nothing crosses the
    host link: a sweep's lanes all start from this one empty state."""
    lead = () if lanes is None else (lanes,)
    shE, shA = shardings or (None, None)
    with jax.enable_x64(True):
        return (jnp.zeros((*lead, rows, cols), jnp.float64, device=shE),
                jnp.full((*lead, rows), -1, jnp.int32, device=shA))


def state_to_device(
    state: CacheState, n: int, layout: StateLayout | str | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Numpy ``CacheState`` -> padded device-layout arrays."""
    E0, a0 = fresh_state_arrays(n, state.m, layout)
    k = state.partition.k
    E0[:k, : state.m] = state.E
    a0[:k] = state.anchor
    return E0, a0


def pad_spec_cols(spec: dict, ncol: int) -> dict:
    """Pad the per-server spec arrays to a layout's column count.

    Bucketed cohorts only share a compiled scan if EVERY input shape
    matches — the state dims come from the layout, but ``dt``/``lam_j``/
    ``mu_j`` are (m,) per scenario.  Edge-replicating them to ``ncol``
    is free (padded entries are never gathered: every ``j`` index in the
    schedule is < m) and lets two points with different real m share one
    cohort."""
    out = dict(spec)
    for key in ("dt", "lam_j", "mu_j"):
        a = np.asarray(spec[key])
        w = ncol - a.shape[-1]
        if a.ndim and w > 0:
            out[key] = np.concatenate(
                [a, np.repeat(a[..., -1:], w, axis=-1)], axis=-1)
    return out


def apply_acc(costs: CostBreakdown, schedule: ReplaySchedule,
              acc: np.ndarray) -> CostBreakdown:
    """Fold one scenario's device accumulator + host counters into costs."""
    costs.transfer += float(acc[0])
    costs.caching += float(acc[1])
    costs.keepalive_rent += float(acc[2])
    costs.n_misses += int(acc[3])
    costs.n_hits += int(acc[4])
    costs.items_transferred += int(acc[5])
    costs.n_requests += schedule.n_requests
    costs.n_item_requests += schedule.n_item_requests
    return costs


# ---------------------------------------------------------------------------
# drop-in engine + offline driver
# ---------------------------------------------------------------------------
class JaxReplayEngine:
    """``ReplayEngine.replay``-compatible driver backed by the jit'd scan.

    Wraps (or builds) a NumPy :class:`~repro.core.engine.ReplayEngine` that
    holds configuration, cache state and costs; ``replay`` builds the host
    schedule from the wrapped engine's CURRENT state, runs the device scan,
    and syncs state + costs back — so snapshots, ``install_partition`` and
    any later numpy-engine use observe exactly what a numpy replay would
    have produced (state float-for-float; cost sums at 1e-9).
    """

    def __init__(self, *args, engine: ReplayEngine | None = None,
                 layout: StateLayout | str | None = None, **kwargs):
        self.engine = engine if engine is not None else ReplayEngine(
            *args, **kwargs)
        self.layout = StateLayout.resolve(layout)
        # fail fast on cost models the device hooks cannot express
        self._spec, self._statics = cost_spec(
            self.engine.model, self.engine.env)
        ncol = self.layout.state_cols(self.engine.env.m)
        if ncol != self.engine.env.m:
            self._spec = pad_spec_cols(self._spec, ncol)

    # delegated views (the engine object stays the source of truth)
    @property
    def state(self) -> CacheState:
        return self.engine.state

    @property
    def costs(self) -> CostBreakdown:
        return self.engine.costs

    @property
    def env(self) -> CacheEnvironment:
        return self.engine.env

    @property
    def model(self) -> CostModel:
        return self.engine.model

    def install_partition(self, *a, **k) -> None:
        self.engine.install_partition(*a, **k)

    def replay(
        self,
        trace,
        clique_generator=None,
        t_cg: float | None = None,
        batch_size: int | None = None,
        *,
        next_cg0: float | None = None,
        win_prefix: tuple[np.ndarray, np.ndarray] | None = None,
    ) -> CostBreakdown:
        eng = self.engine
        keep_fn = None
        if clique_generator is not None and t_cg is not None:
            pol = getattr(clique_generator, "__self__", None)
            keep_fn = getattr(pol, "item_keep", None)
            # device-resident CGM (DESIGN.md §11): when the generator is
            # an unmodified AKPC ``on_window`` the whole merge/split loop
            # runs inside the scan — raw request tensors go up, costs
            # come back, zero host clique-generation calls
            if pol is not None:
                from .cgm_jax import replay_cgm, wants_device_cgm

                # the fused CGM scan keeps a dense-n carry of its own
                # regardless of the session layout (compact (h, h) CRM
                # workspace + (n+1,)-row state it builds via
                # ``state_to_device``), so any single-shard layout —
                # dense or bucketed — may take the device path
                if wants_device_cgm(pol, trace, eng.model) \
                        and self.layout.supports_device_cgm(
                            eng.env.n, eng.env.m):
                    return replay_cgm(
                        self, pol, trace, t_cg=t_cg,
                        batch_size=batch_size, next_cg0=next_cg0,
                        win_prefix=win_prefix)
        schedule = build_schedule(
            eng.state.partition, trace, clique_generator, t_cg,
            model=eng.model, env=eng.env, batch_size=batch_size,
            seed_new_cliques=eng.seed_new_cliques,
            next_cg0=next_cg0, win_prefix=win_prefix, lookup=eng._lookup,
            layout=self.layout,
        )
        # shape-stability ratchet: pad every chunk's tensors up to the
        # largest dims this engine has seen, so a streamed session (ragged
        # tail chunks included) reuses one compiled scan instead of
        # recompiling per chunk shape (tests/test_serving_live.py)
        dims = schedule_dims(schedule)
        prev = getattr(self, "_dims", None)
        if prev is not None:
            dims = {k: max(dims[k], prev[k]) for k in dims}
        self._dims = dims
        schedule = pad_schedule(schedule, dims)
        self.last_schedule = schedule
        E0, a0 = state_to_device(eng.state, schedule.n, self.layout)
        E, anchor, acc = run_schedule(
            schedule, self._spec, self._statics, E0, a0,
            charge=eng.caching_charge, layout=self.layout)
        part = schedule.final_partition
        eng.state = CacheState.from_device(part, E, anchor, eng.m)
        eng._set_partition_caches(part)
        apply_acc(eng.costs, schedule, acc)
        if keep_fn is not None:
            # boundary evictions already ran on device; this only aligns
            # the numpy engine's mask for any later host-side feed()
            eng.set_item_keep(keep_fn(), evict=False)
        return eng.costs


def run_policy_jax(policy, trace, *, batch_size=None, layout=None):
    """Offline driver on the JAX backend — ``run_policy(backend="jax")``.

    Mirrors :func:`repro.core.policy.run_policy` step for step (policy
    bind, environment resolution, offline initial partition, T_CG window
    replay), swapping the replay core for the device scan.
    """
    import time as _time

    from .policy import RunResult, get_policy

    if isinstance(policy, str):
        policy = get_policy(policy)
    t0 = _time.perf_counter()
    policy.bind(trace.n, trace.m)
    env = CacheEnvironment.resolve(
        getattr(policy, "env", None), trace, policy.params)
    eng = JaxReplayEngine(
        trace.n,
        trace.m,
        policy.params,
        caching_charge=getattr(policy, "caching_charge", "requested"),
        seed_new_cliques=getattr(policy, "seed_new_cliques", True),
        env=env,
        cost_model=getattr(policy, "cost_model", "table1"),
        layout=layout,
    )
    part0 = (
        policy.initial_partition(trace)
        if hasattr(policy, "initial_partition") else None
    )
    if part0 is not None:
        eng.install_partition(part0, now=0.0)
    gen = policy.on_window if policy.t_cg is not None else None
    bs = batch_size if batch_size is not None else getattr(
        policy, "batch_size", None)
    eng.replay(trace, clique_generator=gen, t_cg=policy.t_cg,
               batch_size=bs)
    return RunResult(
        policy=policy.name,
        costs=eng.costs,
        clique_sizes=eng.state.partition.sizes(),
        size_history=list(getattr(policy, "size_history", [])),
        n_windows=getattr(policy, "n_windows", 0),
        cg_seconds=getattr(policy, "cg_seconds", 0.0),
        wall_seconds=_time.perf_counter() - t0,
        config=getattr(policy, "config", None),
    )
