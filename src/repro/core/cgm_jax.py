"""Device-resident clique generation: the CGM inside the jit'd scan.

PR 5 moved the replay *state* recurrence on device but left the Clique
Generation Module (Alg. 2-4) on host; PR 6 re-cut that seam (DESIGN.md
§11) so the host ships only RAW request tensors and the scan carry
grows the full CGM state.  This revision re-expresses every boundary
tensor in a COMPACT HOT SPACE (DESIGN.md §15): the paper's CGM only
ever reasons over the window hot set — a ``top_frac`` slice of the
window's distinct items (§V.A) — so the carry holds an ``(h, h)`` CRM
workspace plus an ``(h,)`` hot->catalog index map, with ``h`` the
padded hot-set capacity derived from ``top_frac`` and the window size
(typically ≪ n).  Requests are buffered per window (``wbuf``) and the
CRM is built ONCE per boundary as a rank-``wcap`` update over the hot
incidence — there is no per-step (n, n) matmul and no (n, n) carry at
all.  At each boundary step a ``lax.cond`` branch runs, entirely on
device:

* Alg. 2 — hot set (stable rank of window counts), the ``(h, h)`` CRM
  via ``H^T H`` over the buffered window (``kernels/crm_update.py`` on
  TPU, a fused jnp contraction elsewhere), min-max normalise, binarise
  at theta;
* Alg. 4 — the edge diff vs the previous window's binary CRM via
  cross-space index luts (each side stays ``(h, h)``), then the
  removed-edge splits / added-edge merges as bounded ``fori_loop``s
  over the global slot map with ``(h,)`` side-weight accumulators;
* Alg. 3 — oversized-clique splits as a LIFO worklist over
  fixed-capacity MEMBER LISTS (``gcap`` ≤ a few × omega, not n), and
  the approximate merge as a ``lax.while_loop`` over the thresholded
  union edge-count matrix (order-equivalent to the host's densities)
  in an ``(S_h, S_h)`` act-compacted slot space using
  the incremental ``X = M A M^T`` patch algebra of PR 3
  (``kernels/merge_step.py`` builds the initial D on TPU);
* the partition install (``install_partition``) as segment reductions
  over the old slot map — matching, member-wise expiry min, Alg.-1
  window seeding.

Because events are CONSTRUCTED in-scan (dedup, sort orders, lags — the
``batch_events`` pipeline as jnp sorts/segment-sums), the schedule is
partition-free: theta / gamma / omega / top_frac are runtime scalars
(``cgm_spec``) and a fig7 hyperparameter grid vmaps over them sharing
ONE schedule and ONE host->device transfer per trace (``h`` is sized
by the MAX hot dimension over the vmapped lanes).

Parity bar: the host path (``core/cliques.py`` + the ``cliques_ref``
oracle) stays frozen; device partitions are element-for-element equal
across chained windows and costs match the numpy engine at 1e-9.  The
proof obligations (op-for-op float semantics, stable-sort
tie-breaking, compact-space vs list-order equivalence) are documented
inline at each step.  The f32 CRM / X counters are exact integers
below 2**24 — ``_window_crm_device`` raises if the window capacity
could overflow that bound, and the eligibility gate
(``wants_device_cgm``) sizes ``h`` before routing.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.merge_step import (
    merge_density_auto,
    merge_density_jnp,
    merge_edge_floor,
)
from .cliques import CliquePartition
from .crm import WindowCRM
from .engine import CacheState
from .engine_jax import (
    N_ACC,
    NE_TARGET,
    _bucket,
    _rate_hook,
    _transfer_hook,
    enable_compile_cache,
)

#: device CGM is gated on the PADDED HOT CAPACITY h, not the catalog
#: size — the (h, h) workspace and (2h, 2h) merge matrices stay cheap
#: and the f32 edge counters stay exact for any h below this bound
MAX_DEVICE_CGM_HOT = 2048
#: the hot capacity h is padded up to a multiple of this many slots
HOT_BUCKET = 32
#: f32 exactness bound for the CRM / X integer counters
_F32_EXACT = 1 << 24


def hot_capacity(n: int, max_slots: int, hot_dims) -> int:
    """Padded hot-set capacity for a window of ``max_slots`` item slots.

    ``hot_dims`` is a list of ``(top_frac, of_catalog)`` pairs — one per
    vmapped scenario lane; the capacity is the max over lanes.  The hot
    set requires a positive window count, so it can never exceed the
    window's distinct support (≤ ``max_slots``) even when ``top_frac``
    is taken of the catalog; the bucket keeps recompiles rare.
    """
    need = 1
    for frac, of_catalog in hot_dims:
        base = n if of_catalog else min(n, int(max_slots))
        need = max(need, min(n, int(max_slots),
                             max(1, int(round(base * float(frac))))))
    return min(n, _bucket(need, HOT_BUCKET, HOT_BUCKET))


def _max_window_requests(trace, t_cg: float) -> int:
    """Upper bound on request rows in any one T_CG window.

    Every window's requests lie inside a half-open span of length
    ``t_cg`` starting at a request time (boundaries fire at request
    times and the grid advances by ``t_cg``), so the sliding-window
    count over request-aligned starts dominates all real windows —
    including the open tail window.
    """
    times = np.asarray(trace.times, np.float64)
    if times.size == 0:
        return 0
    ends = np.searchsorted(times, times + float(t_cg), side="left")
    return int((ends - np.arange(times.size)).max())


# ---------------------------------------------------------------------------
# the partition-free schedule: raw request tensors + boundary flags
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class CGMSchedule:
    """Raw request batches of one trace, cut on the T_CG grid.

    Unlike :class:`~repro.core.engine_jax.ReplaySchedule` there are no
    event tensors and no install records — events and partitions are
    derived ON DEVICE.  ``xs`` leading axis is nb (scan steps); a step
    never straddles a T_CG boundary, and a step whose window begins a
    new T_CG period carries ``cg=True`` + the boundary evaluation time.
    ``h`` / ``wcap`` size the compact boundary workspace: padded hot
    capacity and the window request-row buffer (``win_rows`` /
    ``win_slots`` record the raw per-window maxima they derive from).
    """

    n: int
    m: int
    nb: int
    B: int                      # requests per step (padded)
    d: int                      # item slots per request
    const_dt: bool              # device CGM requires uniform dt
    uses_sizes: bool
    xs: dict
    n_requests: int
    n_item_requests: int
    boundary_steps: np.ndarray  # (n_boundaries,) scan-step indices
    win_start: int              # open-window start index into the trace
    boundary_hit: bool
    next_cg: float | None
    h: int                      # padded hot-set capacity
    wcap: int                   # window request-row buffer capacity
    win_rows: int               # max request rows in any one window
    win_slots: int              # max item slots in any one window (≤ n)


def build_cgm_schedule(
    trace,
    t_cg: float,
    *,
    uses_sizes: bool,
    batch_size: int | None = None,
    next_cg0: float | None = None,
    hot_dims=None,
    prefix_rows: int = 0,
    prefix_slots: int = 0,
) -> CGMSchedule:
    """Cut the trace into boundary-aligned request batches.

    The walk is the same T_CG grid as ``build_schedule`` (and the numpy
    ``ReplayEngine.replay``): a boundary fires when the next request
    lies at/after ``next_cg``, is evaluated at that request's time, and
    empty periods are skipped with a single firing.  No clique
    generation happens here — the boundary merely flags the step.

    ``hot_dims`` is the ``(top_frac, of_catalog)`` list over the lanes
    that will share this schedule (default: a full-support lane, the
    conservative ``h`` = window support); ``prefix_rows`` /
    ``prefix_slots`` account a session's already-open window so the
    head window's buffer capacity covers it.
    """
    times, servers, items = trace.times, trace.servers, trace.items
    R = int(times.shape[0])
    d = int(items.shape[1]) if items.ndim == 2 else 1
    if batch_size is not None:
        bs = max(1, int(batch_size))
    else:
        bs = max(1, NE_TARGET // max(1, d))
    if R > 0:
        next_cg = (float(next_cg0) if next_cg0 is not None
                   else float(times[0]) + t_cg)
    else:
        next_cg = next_cg0 if next_cg0 is not None else np.inf

    slices: list[tuple[int, int, float | None]] = []
    pending_cg: float | None = None
    win_start = 0
    boundary_hit = False
    pos = 0
    while pos < R:
        cut = int(np.searchsorted(times, next_cg, side="left"))
        if cut <= pos:
            t = float(times[pos])
            pending_cg = t
            win_start = pos
            boundary_hit = True
            while next_cg <= t:
                next_cg += t_cg
            continue
        stop = min(pos + bs, cut)
        slices.append((pos, stop, pending_cg))
        pending_cg = None
        pos = stop

    nb_raw = max(1, len(slices))
    nb = _bucket(nb_raw, 4, 4)
    B = _bucket(max((s - p for p, s, _ in slices), default=1), 32, 32)

    # per-window row/slot accounting: a boundary slice CLOSES the window
    # accumulated so far (head window includes the session prefix; the
    # tail window stays open but still occupies the buffer)
    cur_rows, cur_slots = int(prefix_rows), int(prefix_slots)
    max_rows, max_slots = cur_rows, cur_slots
    for p, s, cg_now in slices:
        if cg_now is not None:
            cur_rows, cur_slots = 0, 0
        cur_rows += s - p
        cur_slots += (s - p) * d
        max_rows = max(max_rows, cur_rows)
        max_slots = max(max_slots, cur_slots)
    win_slots = min(trace.n, max_slots)
    # +B headroom: a step writes its whole padded block at offset wlen
    # before the validity mask trims it, so the buffer must absorb one
    # full batch past the worst window
    wcap = _bucket(max_rows + B, 64, 64)
    if hot_dims is None:
        hot_dims = [(1.0, False)]
    h = hot_capacity(trace.n, win_slots, hot_dims)

    t_pad = float(times[-1]) if R else 0.0
    xs = {
        "items": np.full((nb, B, d), -1, np.int32),
        "servers": np.zeros((nb, B), np.int32),
        "times": np.full((nb, B), t_pad, np.float64),
        "cg": np.zeros(nb, bool),
        "now": np.zeros(nb, np.float64),
        "nreq": np.zeros(nb, np.int32),
    }
    boundary_steps = []
    for b, (p, s, cg_now) in enumerate(slices):
        w = s - p
        xs["items"][b, :w] = items[p:s]
        xs["servers"][b, :w] = servers[p:s]
        xs["times"][b, :w] = times[p:s]
        xs["times"][b, w:] = times[s - 1]
        xs["nreq"][b] = w
        if cg_now is not None:
            xs["cg"][b] = True
            xs["now"][b] = cg_now
            boundary_steps.append(b)

    return CGMSchedule(
        n=trace.n, m=trace.m, nb=nb, B=B, d=d, const_dt=True,
        uses_sizes=uses_sizes, xs=xs,
        n_requests=R, n_item_requests=int((items >= 0).sum()),
        boundary_steps=np.asarray(boundary_steps, np.int32),
        win_start=win_start, boundary_hit=boundary_hit,
        next_cg=None if R == 0 else float(next_cg),
        h=h, wcap=wcap, win_rows=max_rows, win_slots=win_slots,
    )


def pad_cgm_schedule(schedule: CGMSchedule, dims: dict) -> CGMSchedule:
    """Pad a CGM schedule's xs + capacities up to shared ``dims``.

    The device-CGM analogue of ``engine_jax.pad_schedule`` — cohort
    alignment (sweep) and the live ratchet reuse ONE compiled scan
    across schedules by padding to the running max dims ``{"nb", "B",
    "d", "h", "W"}``.  Growing B also grows the per-step block write,
    so ``wcap`` is re-derived to keep ``win_rows + B <= wcap``.
    """
    s = schedule
    nb = max(dims.get("nb", s.nb), s.nb)
    B = max(dims.get("B", s.B), s.B)
    d = max(dims.get("d", s.d), s.d)
    h = max(dims.get("h", s.h), s.h)
    wcap = max(dims.get("W", s.wcap), s.wcap,
               _bucket(s.win_rows + B, 64, 64))
    if (nb, B, d) == (s.nb, s.B, s.d) and (h, wcap) == (s.h, s.wcap):
        return s
    xs0 = s.xs
    if (nb, B, d) != (s.nb, s.B, s.d):
        t_pad = float(xs0["times"][-1, -1]) if s.nb else 0.0
        items = np.full((nb, B, d), -1, np.int32)
        items[: s.nb, : s.B, : s.d] = xs0["items"]
        servers = np.zeros((nb, B), np.int32)
        servers[: s.nb, : s.B] = xs0["servers"]
        times = np.full((nb, B), t_pad, np.float64)
        times[: s.nb, : s.B] = xs0["times"]
        # padded request slots reuse the step's last real time so the
        # in-scan dedup keys stay inert
        times[: s.nb, s.B:] = xs0["times"][:, -1:]
        cg = np.zeros(nb, bool)
        cg[: s.nb] = xs0["cg"]
        now = np.zeros(nb, np.float64)
        now[: s.nb] = xs0["now"]
        nreq = np.zeros(nb, np.int32)
        nreq[: s.nb] = xs0["nreq"]
        xs = dict(items=items, servers=servers, times=times, cg=cg,
                  now=now, nreq=nreq)
    else:
        xs = xs0
    return dataclasses.replace(s, nb=nb, B=B, d=d, xs=xs, h=h, wcap=wcap)


def cgm_spec(cfg, params, n: int) -> dict:
    """The CGM hyperparameters as runtime (vmappable) scalars.

    theta enters an f32 comparison on the host path (NEP-50 weak scalar
    against the f32 CRM), so it is shipped as f32; gamma's f32 density
    bar is shipped as the equivalent union edge-count floor.
    """
    omega = int(params.omega) if cfg.enable_split else int(n)
    return {
        "theta": np.float32(params.theta),
        "e_floor": merge_edge_floor(omega, params.gamma),
        "gamma": np.float64(params.gamma),
        "omega": np.int32(omega),
        "omega_f": np.float64(omega),
        "top_frac": np.float64(cfg.top_frac),
        "of_catalog": np.bool_(cfg.top_frac_of == "catalog"),
    }


# ---------------------------------------------------------------------------
# device: window accumulation (Alg. 2 running state)
# ---------------------------------------------------------------------------
def _accumulate_window(carry, x, *, n, m):
    """Fold one request batch into the open window's buffers.

    * ``wbuf`` (wcap, dbuf) i32 — the window's raw request rows; the
      whole padded block lands at offset ``wlen`` and ``wlen`` advances
      by the step's VALID row count only, so pad rows are overwritten
      by the next step and anything at/after ``wlen`` is stale by
      construction.  The CRM is built from this buffer ONCE per
      boundary (no per-step (n, n) matmul).
    * ``wcnt`` (n+1,) i32 — per-item access counts WITH duplicates
      (the host hot-set bincount does not dedup within a request).
    * ``seed`` (n+1, m) i32 — (item, server) counts WITH duplicates
      (the per-occurrence tally of ``window_seed_servers``).
    """
    items = x["items"]                              # (B, d) i32
    B, d = items.shape
    dbuf = carry["wbuf"].shape[1]
    if d < dbuf:
        items_b = jnp.pad(items, ((0, 0), (0, dbuf - d)),
                          constant_values=-1)
    else:
        items_b = items
    wbuf = jax.lax.dynamic_update_slice(
        carry["wbuf"], items_b, (carry["wlen"], jnp.int32(0)))
    wlen = carry["wlen"] + x["nreq"]
    valid = items >= 0
    col = jnp.where(valid, items, n)                # invalid -> dump col n
    wcnt = carry["wcnt"].at[col.reshape(-1)].add(1)[: n + 1]
    seed = carry["seed"].at[col, x["servers"][:, None]].add(
        valid.astype(jnp.int32))
    return dict(carry, wbuf=wbuf, wlen=wlen, wcnt=wcnt, seed=seed)


# ---------------------------------------------------------------------------
# device: compact-space primitives
# ---------------------------------------------------------------------------
def _compact_indices(mask, size):
    """Ascending indices of True entries, padded with ``len(mask)``.

    The cumsum/scatter form of ``jnp.nonzero(mask, size=size,
    fill_value=len(mask))`` — nonzero's static-size lowering sorts the
    whole mask (O(n log n) per call, ~260us at n=4096 on CPU), which
    dominates when called inside the per-edge adjust loops; this stays
    O(n).  Entries past ``size`` collapse onto the scatter dump slot.
    """
    n = mask.shape[0]
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    idx = jnp.where(mask & (pos < size), pos, size)
    return jnp.full(size + 1, n, jnp.int32).at[idx].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")[:size]


def _capped_true_indices(mask, cap, bs=128):
    """Flat indices of the first ``cap`` True entries (pads = len(mask)).

    Gather-based two-level stream compaction: per-block popcounts pick
    each target's block by vectorized binary search, then a (cap, bs)
    row gather ranks within the block — O(n + cap*bs) elementwise work
    with NO large scatter (XLA CPU scatter runs ~55ns/element, which
    makes ``_compact_indices`` over an (h, h) mask cost ~80ms at
    h~1200; this path is ~2ms).  Targets past the population count pad
    with ``len(mask)``.
    """
    n = mask.shape[0]
    nb = -(-n // bs)
    pad = nb * bs - n
    if pad:
        mask = jnp.concatenate([mask, jnp.zeros(pad, bool)])
    blk = mask.reshape(nb, bs).astype(jnp.int32)
    coff = jnp.cumsum(blk.sum(axis=1))               # (nb,) inclusive
    k = jnp.arange(1, cap + 1, dtype=jnp.int32)      # 1-based targets
    b = jnp.searchsorted(coff, k, side="left").astype(jnp.int32)
    bc = jnp.minimum(b, nb - 1)
    t = k - jnp.where(bc > 0, coff[jnp.maximum(bc - 1, 0)], 0)
    rcs = jnp.cumsum(blk[bc], axis=1)                # (cap, bs)
    pos = (rcs < t[:, None]).sum(axis=1).astype(jnp.int32)
    return jnp.where(b < nb, bc * bs + pos, n)


def _true_indices(mask, size, cap):
    """``_compact_indices(mask, size)`` with a fast common case.

    ``cap`` is a static bound on the EXPECTED population count: within
    it, the gather-based capped compaction fills the (size,) buffer; a
    rare overflow falls back (``lax.cond``, so only the taken branch
    runs) to the exact O(n)-scatter form.  Returns ``(indices, count)``.
    """
    n = mask.shape[0]
    cnt = mask.sum().astype(jnp.int32)
    if cap >= size:
        return _compact_indices(mask, size), cnt
    idx = jax.lax.cond(
        cnt > cap,
        lambda: _compact_indices(mask, size),
        lambda: jnp.full(size, n, jnp.int32).at[:cap].set(
            _capped_true_indices(mask, cap)))
    return idx, cnt


def _member_lists(of, n, gcap):
    """(n+1, gcap) member lists of every group: ascending ids, pads = n.

    One stable argsort + rank-in-run scatter builds ALL lists at once —
    the per-edge adjust loops then gather a (gcap,) row in O(gcap)
    instead of recomputing ``of == g`` compactions per edge (each of
    which pays an O(n) scatter, ~250us at n=4096 on CPU).  Groups wider
    than ``gcap`` cannot exist here (the ``_split_oversized`` invariant);
    their overflow updates drop defensively.  Row ``n`` stays all-pads —
    the dump row for predicated in-loop updates.
    """
    order = jnp.argsort(of).astype(jnp.int32)        # stable: ids ascend
    og = of[order]
    iota = jnp.arange(n, dtype=jnp.int32)
    newrun = jnp.concatenate([jnp.ones(1, bool), og[1:] != og[:-1]])
    start = jax.lax.cummax(jnp.where(newrun, iota, 0))
    return jnp.full((n + 1, gcap), n, jnp.int32).at[
        og, iota - start].set(order, mode="drop")


def _dense_rank(keys):
    """Dense rank (0..k-1) of each entry by ascending key value."""
    sk = jnp.sort(keys)
    first = jnp.concatenate([jnp.ones(1, bool), sk[1:] != sk[:-1]])
    rnk = (jnp.cumsum(first.astype(jnp.int32)) - 1).astype(jnp.int32)
    pos = jnp.searchsorted(sk, keys)
    return rnk[pos]


def _split_sides_compact(W, member, u, v, cap):
    """``split_clique_on_edge`` over a compact member mask: True = right.

    ``W`` is a (cap, cap) weight matrix in the compact space (hot slots
    or a member-list submatrix); ``u`` / ``v`` are compact indices and
    may be -1 for an endpoint that is COLD in the current window (zero
    weight column on the host's ``_CrmView``) — its side accumulator
    starts at zero and, for ``v``, the caller re-seeds the right side
    in global coordinates.  Bit-exact vs the host: the f64 side-weight
    accumulators update in ascending compact order (ascending item id
    in both spaces), the tie ``wl[p] >= wr[p]`` sends p left, and cold
    members (zero column, zero accumulated weight) tie left with zero
    contribution — the host's in-order no-op.
    """
    wl0 = jnp.where(u >= 0, W[:, jnp.maximum(u, 0)], 0.0)
    wr0 = jnp.where(v >= 0, W[:, jnp.maximum(v, 0)], 0.0)
    right0 = jnp.arange(cap, dtype=jnp.int32) == v

    def body(p, st):
        wl, wr, right = st
        act = member[p] & (p != u) & (p != v)
        go_left = wl[p] >= wr[p]
        right = right.at[p].set(jnp.where(act & ~go_left, True, right[p]))
        colp = W[:, p]
        wl = jnp.where(act & go_left, wl + colp, wl)
        wr = jnp.where(act & ~go_left, wr + colp, wr)
        return (wl, wr, right)

    _, _, right = jax.lax.fori_loop(0, cap, body, (wl0, wr0, right0))
    return right & member


def _window_crm_device(carry, cspec, *, n, h, wcap, use_kernels):
    """Alg. 2 at a boundary: hot set -> compact CRM -> binarise.

    Returns ``(hot_idx, valid_h, lut, raw, norm, binary)`` — the
    ascending hot->catalog index map (pads = n), its validity mask, the
    catalog->hot lut (cold/pad -> -1) and the (h, h) raw/norm/binary
    CRM.  Ascending ``hot_idx`` IS the host's compact hot-space order,
    so every comparison downstream sees the same values in the same
    scan order.  Raw counts are exact f32 integers: each pair count is
    bounded by the window row count ≤ wcap, guarded below.
    """
    if wcap >= _F32_EXACT:
        raise ValueError(
            f"device CGM window capacity wcap={wcap} reaches the f32 "
            f"exact-integer bound 2**24; co-occurrence counts could "
            "silently lose exactness — route this trace to the host CGM "
            "(or lower the clique-generation period t_cg)")
    counts = carry["wcnt"][:n]                       # (n,) i32
    support = (counts > 0).sum()
    base = jnp.where(cspec["of_catalog"], n, support).astype(jnp.float64)
    # host: max(1, int(round(base * top_frac))) — np.round is half-even,
    # same as Python's round
    n_hot = jnp.maximum(
        1, jnp.round(base * cspec["top_frac"])).astype(jnp.int32)
    order = jnp.argsort(-counts)                     # stable: ties -> low id
    rank = jnp.zeros(n, jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    hot = (rank < n_hot) & (counts > 0)
    # ascending hot ids = the host hot_items order (sorted); capacity h
    # dominates every real window by construction (hot_capacity)
    hot_idx = _compact_indices(hot, h)
    valid_h = hot_idx < n
    lut = jnp.full(n + 1, -1, jnp.int32).at[hot_idx].set(
        jnp.arange(h, dtype=jnp.int32)).at[n].set(-1)

    # compact CRM from the buffered window: one rank-wcap update
    wbuf = carry["wbuf"]                             # (wcap, dbuf) i32
    dbuf = wbuf.shape[1]
    rowi = jax.lax.broadcasted_iota(jnp.int32, (wcap, dbuf), 0)
    live = (rowi < carry["wlen"]) & (wbuf >= 0)
    hs = lut[jnp.where(live, wbuf, n)]               # hot slot or -1
    hcol = jnp.where(hs >= 0, hs, h)                 # cold/stale -> dump col
    if use_kernels:
        from ..kernels.crm_update import crm_update_auto

        H = jnp.zeros((wcap, h + 1), jnp.float32).at[rowi, hcol].set(1.0)
        raw = crm_update_auto(H[:, :h])              # (h, h) f32, zero diag
    elif h * h <= 1600 * dbuf * dbuf:
        # small hot space: the dense H^T H contraction beats per-pair
        # scatter updates (XLA CPU scatter runs ~55ns/element serial,
        # SIMD matmul ~0.03ns/flop — crossover near h ~ 40 dbuf).  The
        # equality broadcast dedups in-row repeats for free, and 0/1
        # dots over <= wcap rows stay exact f32 integers.
        Hf = (hcol[:, :, None] == jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, h), 2)).any(axis=1).astype(jnp.float32)
        raw = Hf.T @ Hf
        raw = raw * (1.0 - jnp.eye(h, dtype=jnp.float32))
    else:
        # pair-scatter form of the H^T H contraction: each request row
        # holds <= dbuf items, so scattering its dbuf^2 hot pairs costs
        # O(wcap d^2) instead of the O(wcap h^2) matmul — the big-h
        # CPU/GPU fallback; the Mosaic kernel above keeps the
        # MXU-shaped matmul.  In-row duplicates collapse to the dump
        # column first (the H one-hot .set dedup), so counts stay the
        # exact 0/1 contraction.
        sc = jnp.sort(hcol, axis=1)
        dup = jnp.concatenate(
            [jnp.zeros((wcap, 1), bool), sc[:, 1:] == sc[:, :-1]], axis=1)
        sc = jnp.where(dup | (sc >= h), h, sc)
        raw = jnp.zeros((h + 1, h + 1), jnp.float32).at[
            sc[:, :, None], sc[:, None, :]].add(1.0)[:h, :h]
        raw = raw * (1.0 - jnp.eye(h, dtype=jnp.float32))
    hi = raw.max().astype(jnp.float64)
    # host minmax_normalise: lo is always 0 (zero diagonal), hi<=0 -> 0;
    # int64/int64 true-divide (f64) then cast f32 == f32->f64 exact here
    norm = jnp.where(
        hi > 0.0,
        (raw.astype(jnp.float64) / hi).astype(jnp.float32),
        jnp.zeros((h, h), jnp.float32),
    )
    hm2 = valid_h[:, None] & valid_h[None, :]
    binary = (norm > cspec["theta"]) & hm2 & ~jnp.eye(h, dtype=bool)
    return hot_idx, valid_h, lut, raw, norm, binary


# ---------------------------------------------------------------------------
# device: Alg. 4 adjust + Alg. 3 split/merge in the compact hot space
# ---------------------------------------------------------------------------
def _adjust_partition(of, gsize, binary, W, hot_idx, valid_h, lut,
                      addM, remM, rem_map, cspec, *, n, h, gcap):
    """Alg. 4 (``adjust_previous_cliques``) over slot buffers.

    Slot numbering mirrors the host list exactly: removed-edge splits
    keep the left side in the parent slot and append the right side at
    ``ngroups`` (the host's ``groups.append``); added-edge merges keep
    ``min(cu, cv)`` and kill ``max`` (the host's keep/drop).  Both loop
    bodies stay O(n + h) per edge: splits run on the group's
    fixed-capacity MEMBER LIST (``gcap`` bounds any group size here —
    the ``_split_oversized`` invariant), and the merge probe reads a
    clique-pair edge-count matrix built once after the removals and
    folded row/col per accepted merge instead of re-reducing the (h, h)
    CRM per edge.  Cold members have zero weight columns and tie left
    (the host no-op); edge endpoints always sit in the member list, so
    the right side seeds from ``v`` even when ``v`` went cold.  The
    final compaction ranks alive slots ascending — the host's ``[g for
    g in groups if g]`` order.
    """
    ngroups = (gsize > 0).sum().astype(jnp.int32)
    ml = _member_lists(of, n, gcap)
    pads_g = jnp.full(gcap, n, jnp.int32)
    ecap = max(1, h * (h - 1) // 2)
    dcap = min(ecap, _bucket(2 * h, 256, 256))

    # EXACT no-op prefilter: during the rem phase groups only SPLIT, so
    # an edge whose endpoints sit in different groups now can never be
    # same-group when its turn comes — drop it before the sequential
    # loop.  The host walks those edges too, as no-ops; the survivor
    # subset keeps its lexicographic order, so state updates agree
    # edge for edge.  Flat row-major compaction == nonzero's edge
    # order; pathological diff churn falls back to the exact scatter
    # compaction inside _true_indices.
    og_p = of[jnp.clip(rem_map, 0, n - 1)]           # group per prev slot
    remM = remM & (og_p[:, None] == og_p[None, :])
    rem_f, n_rem = _true_indices(remM.reshape(-1), ecap, dcap)

    def rem_body(i, st):
        of, gsize, ngroups, ml = st
        fi = rem_f[i]                                # flat (h, h) prev-edge
        u = rem_map[fi // h]
        v = rem_map[fi % h]
        cu = of[u]
        do = (cu == of[v]) & (gsize[cu] > 1)
        mem = ml[cu]                                 # (gcap,) ascending ids
        gvalid = mem < n
        gh = lut[mem]                                # hot slot or -1
        ghc = jnp.maximum(gh, 0)
        okw = (gh >= 0)[:, None] & (gh >= 0)[None, :]
        Wsub = jnp.where(okw, W[ghc][:, ghc], 0.0)
        pu = jnp.argmax(mem == u).astype(jnp.int32)
        pv = jnp.argmax(mem == v).astype(jnp.int32)
        right_g = _split_sides_compact(Wsub, gvalid, pu, pv, gcap) & do
        nr = right_g.sum().astype(jnp.int32)
        of = of.at[jnp.where(right_g, mem, n)].set(ngroups, mode="drop")
        g2 = gsize.at[cu].add(-nr).at[ngroups].set(nr)
        gsize = jnp.where(do, g2, gsize)
        lit = jnp.sort(jnp.where(gvalid & ~right_g, mem, n))
        rit = jnp.sort(jnp.where(right_g, mem, n))
        ml = ml.at[jnp.where(do, cu, n)].set(lit)
        ml = ml.at[jnp.where(do, ngroups, n)].set(rit)
        ngroups = ngroups + do.astype(jnp.int32)
        return (of, gsize, ngroups, ml)

    of, gsize, ngroups, ml = jax.lax.fori_loop(
        0, n_rem, rem_body, (of, gsize, ngroups, ml))

    # mirror prefilter for adds: the add phase only MERGES, so an edge
    # whose endpoints already share a group AFTER the rem phase stays
    # same-group forever — a guaranteed no-op on the host walk too
    og_c = of[jnp.clip(hot_idx, 0, n - 1)]           # group per cur slot
    addM = addM & (og_c[:, None] != og_c[None, :])
    add_f, n_add = _true_indices(addM.reshape(-1), ecap, dcap)

    def add_body(i, st):
        of, gsize, ml = st
        fi = add_f[i]                                # flat (h, h) cur-edge
        u = hot_idx[fi // h]
        v = hot_idx[fi % h]
        cu = of[u]
        cv = of[v]
        g = gsize[cu] + gsize[cv]
        # fully_connected: the union's in-edge count must be C(g, 2),
        # probed over the union's MEMBER LISTS (<= 2 gcap slots) in the
        # (h, h) hot space; cold members contribute no edges (lut -> -1
        # rows mask out), so this also rejects unions with cold items —
        # exactly the host probe semantics
        mem = jnp.concatenate([ml[cu], ml[cv]])      # (2 gcap,)
        mh = lut[mem]                                # hot slot or -1
        mhc = jnp.maximum(mh, 0)
        okm = (mh >= 0)[:, None] & (mh >= 0)[None, :]
        ne = (binary[mhc][:, mhc] & okm).sum() // 2
        do = (cu != cv) & (g <= cspec["omega"]) & (ne == g * (g - 1) // 2)
        keep = jnp.minimum(cu, cv)
        drop = jnp.maximum(cu, cv)
        of = of.at[jnp.where(do, mem, n)].set(keep, mode="drop")
        g2 = gsize.at[keep].set(g).at[drop].set(0)
        gsize = jnp.where(do, g2, gsize)
        ml = ml.at[jnp.where(do, keep, n)].set(jnp.sort(mem)[:gcap])
        ml = ml.at[jnp.where(do, drop, n)].set(pads_g)
        return (of, gsize, ml)

    of, gsize, _ = jax.lax.fori_loop(0, n_add, add_body, (of, gsize, ml))

    alive = gsize > 0
    newid = (jnp.cumsum(alive.astype(jnp.int32)) - 1).astype(jnp.int32)
    of = newid[of]
    gsize = jnp.zeros(n + 1, jnp.int32).at[
        jnp.where(alive, newid, n)].add(gsize)[:n]
    return of, gsize


def _split_oversized(of, gsize, W, lut, cspec, *, n, h, gcap):
    """Alg. 3 splits (``split_oversized``) as a bounded LIFO worklist.

    Only oversized slots run the worklist; every other slot keeps its
    pass-through key.  The worklist carries fixed-capacity MEMBER LISTS
    (ascending item ids, pads = n) of width ``gcap`` — an invariant
    bound on any group size at this point (≤ max(initial partition,
    omega) by induction: adjust merges are omega-capped and splits only
    shrink).  Pieces keep the host's IN-PLACE order via the key
    ``slot * (gcap+1) + emit_idx``; the closed-form hot_count<=1 peel
    is subsumed by the generic weakest-edge split: with an all-zero
    weight submatrix the first-min edge is (g[0], g[1]) and every tie
    goes left, which peels exactly the host's ``(g[0],) + g[p+1:]``
    then ``g[p] .. g[1]`` singletons.
    """
    KW = gcap + 1
    triu_g = jnp.triu(jnp.ones((gcap, gcap), bool), k=1)
    over = gsize > cspec["omega"]
    os_idx = _compact_indices(over, n)
    n_os = over.sum()
    ml = _member_lists(of, n, gcap)
    of_key0 = jnp.concatenate(
        [of * KW, jnp.zeros(1, jnp.int32)])          # (n+1,): pass-through

    def slot_body(i, of_key):
        s = os_idx[i]
        mem0 = ml[s]
        stack0 = jnp.full((gcap + 1, gcap), n, jnp.int32).at[0].set(mem0)

        def cond(st):
            return st[0] > 0

        def wbody(st):
            sp, stack, ofk, emit = st
            g = stack[sp - 1]                        # (gcap,) ascending ids
            sp = sp - 1
            gvalid = g < n
            small = gvalid.sum() <= cspec["omega"]
            tgt = jnp.where(gvalid & small, g, n)
            ofk = ofk.at[tgt].set(s * KW + emit)
            emit = emit + small.astype(jnp.int32)
            # weakest edge: first row-major minimum over member pairs —
            # the member list ascends in item id, so this is the host's
            # submatrix argmin scan order; cold members weigh 0
            gh = lut[g]                              # hot slot or -1
            ghc = jnp.maximum(gh, 0)
            okw = (gh >= 0)[:, None] & (gh >= 0)[None, :]
            Wsub = jnp.where(okw, W[ghc][:, ghc], 0.0)
            pairm = gvalid[:, None] & gvalid[None, :] & triu_g
            P = jnp.where(pairm, Wsub, jnp.inf)
            f = jnp.argmin(P.reshape(-1)).astype(jnp.int32)
            u = f // gcap
            v = f % gcap
            right = _split_sides_compact(Wsub, gvalid, u, v, gcap)
            rit = jnp.sort(jnp.where(right, g, n))
            lit = jnp.sort(jnp.where(gvalid & ~right, g, n))
            stack = stack.at[sp].set(jnp.where(small, stack[sp], rit))
            stack = stack.at[sp + 1].set(
                jnp.where(small, stack[sp + 1], lit))
            sp = sp + jnp.where(small, 0, 2)
            return (sp, stack, ofk, emit)

        _, _, of_key, _ = jax.lax.while_loop(
            cond, wbody, (jnp.int32(1), stack0, of_key, jnp.int32(0)))
        return of_key

    of_key = jax.lax.fori_loop(0, n_os, slot_body, of_key0)
    # dense-rank the (slot, emit) keys -> pieces in host list order
    return _dense_rank(of_key[:n])


def _approx_merge(of, binary, hot_idx, valid_h, cspec, *, n, h,
                  use_kernels, full_merge):
    """Alg. 3 approximate merge (``approximate_merge``) as a while_loop.

    The merge works in an ACT-COMPACTED slot space of capacity ``scap``:
    act groups (the host's candidate set with a live hot member) take
    slots 0..n_act-1 in input order, merged groups take tail slots —
    ascending slot order stays the host's compact act-matrix order at
    every iteration, so the row-major first-argmax over D breaks ties
    identically.  Under the pruning regime (omega > 2 and gamma above
    the density bar) at most h groups can be act, so ``scap = 2h``;
    outside it (omega = 2, gamma at or below the bar, the w/o-CS
    ablation) every group is act, so such lanes compile with
    ``full_merge`` -> ``scap = 2n``, which routing keeps within the
    bound the pruned space has (``wants_device_cgm``).
    D uses the sentinel -2.0 for dead / non-act / diagonal entries; X
    is patched incrementally, one row/col per merge (the pair's summed rows),
    with the f32 add order of the host (``(X[ai,ai] + X[aj,aj]) + 2.0 *
    X[ai,aj]``).  Returns the new slot map and the number of merges the
    loop made.
    """
    if h * (h - 1) // 2 >= _F32_EXACT >> 1:
        raise ValueError(
            f"device CGM hot capacity h={h} puts the pairwise edge "
            f"count h*(h-1)/2 at/above 2**23; the merge orders pairs by "
            "f32 edge count, which matches the host's f32 density order "
            "only below that — route this trace to the host CGM")
    scap = 2 * n if full_merge else 2 * h
    slot = jnp.arange(scap, dtype=jnp.int32)
    hot_c = jnp.clip(hot_idx, 0, n - 1)
    hot_of = of[hot_c]                               # (h,) group per hot slot
    sizes_n = jnp.zeros(n + 1, jnp.int32).at[of].add(1)[:n]
    alive_n = sizes_n > 0
    # host _mergeable_split: the hot filter only engages above the
    # density bar (omega > 2 and gamma > (omega-2)/omega)
    prune = (cspec["omega"] > 2) & (
        cspec["gamma"] > (cspec["omega_f"] - 2.0) / cspec["omega_f"])
    has_hot = (jnp.zeros(n + 1, jnp.int32).at[
        jnp.where(valid_h, hot_of, n)].add(1)[:n]) > 0
    live_h = valid_h & binary.any(axis=1)
    has_live = (jnp.zeros(n + 1, jnp.int32).at[
        jnp.where(live_h, hot_of, n)].add(1)[:n]) > 0
    is_rest = alive_n & prune & ~has_hot
    act_n = alive_n & jnp.where(prune, has_live, True) & ~is_rest

    # act groups -> merge slots 0..n_act-1 (input order preserved)
    msl_n = (jnp.cumsum(act_n.astype(jnp.int32)) - 1).astype(jnp.int32)
    n_act0 = act_n.sum().astype(jnp.int32)
    slot_of_m = _compact_indices(act_n, scap)
    # non-act groups park at scap+slot: inert to the loop, recovered in
    # the final ranking
    of2 = jnp.where(act_n[of], msl_n[of], scap + of)
    sizes_pad = jnp.concatenate([sizes_n, jnp.zeros(1, jnp.int32)])
    sizes = sizes_pad[jnp.clip(slot_of_m, 0, n)]     # (scap,) pads -> 0
    alive = slot < n_act0
    act = alive

    # X = M A M^T over hot membership (f32 exact integer counts);
    # M maps merge slots x hot slots (cold members carry no edges)
    hs = jnp.where(valid_h & act_n[hot_of], msl_n[hot_of], scap)
    A = binary.astype(jnp.float32)
    if use_kernels:
        from ..kernels.clique_density import clique_pair_edges_auto

        M = jnp.zeros((scap + 1, h), jnp.float32).at[
            hs, jnp.arange(h, dtype=jnp.int32)].set(1.0)[:scap]
        X = clique_pair_edges_auto(M, A)
    else:
        # edge-scatter form of M A M^T: only binary's TRUE entries
        # scatter (O(h) edges in practice vs h^2 pair updates — XLA CPU
        # scatter is per-element serial, so the full-pair form costs
        # ~80ms at h~1200); dense windows take the exact full-pair
        # fallback.  Identical exact-integer f32 counts either way
        # (every true (k, l) lands on (hs[k], hs[l]); zeros add zero).
        eb_cap = min(h * h, _bucket(4 * h, 1024, 1024))
        ne2 = binary.sum().astype(jnp.int32)

        def x_sparse():
            ef = _capped_true_indices(binary.reshape(-1), eb_cap)
            ok = ef < h * h
            efc = jnp.minimum(ef, h * h - 1)
            sa = jnp.where(ok, hs[efc // h], scap)
            sb = jnp.where(ok, hs[efc % h], scap)
            return jnp.zeros((scap + 1, scap + 1), jnp.float32).at[
                sa, sb].add(jnp.where(ok, 1.0, 0.0))

        def x_dense():
            return jnp.zeros((scap + 1, scap + 1), jnp.float32).at[
                hs[:, None], hs[None, :]].add(A)

        X = jax.lax.cond(ne2 > eb_cap, x_dense, x_sparse)[:scap, :scap]
    # D holds thresholded union edge counts (``merge_step`` docstring):
    # the host's density order and gamma bar, with no device division
    eyeS = jnp.eye(scap, dtype=bool)
    merge_d = merge_density_auto if use_kernels else merge_density_jnp
    D = merge_d(X, sizes, cspec["omega"], cspec["e_floor"])
    actp = act[:, None] & act[None, :] & ~eyeS
    D = jnp.where(actp, D, -2.0)

    tail0 = n_act0

    def cond(st):
        D = st[1]
        n_act = st[7]
        return (n_act >= 2) & (D.max() >= 0.0)

    def body(st):
        X, D, of2, sizes, act, alive, tail, n_act = st
        f = jnp.argmax(D.reshape(-1)).astype(jnp.int32)
        ai = f // scap
        aj = f % scap
        ai, aj = jnp.minimum(ai, aj), jnp.maximum(ai, aj)
        t = tail
        mm = (of2 == ai) | (of2 == aj)
        of2 = jnp.where(mm, t, of2)
        row = X[ai, :] + X[aj, :]
        dg = (X[ai, ai] + X[aj, aj]) + 2.0 * X[ai, aj]
        X = X.at[t, :].set(row).at[:, t].set(row).at[t, t].set(dg)
        gnew = sizes[ai] + sizes[aj]
        sizes = sizes.at[t].set(gnew)
        alive = alive.at[ai].set(False).at[aj].set(False).at[t].set(True)
        act = act.at[ai].set(False).at[aj].set(False).at[t].set(True)
        # the new group's edge-count row, host op order:
        # (within[-1] + within[:-1]) + Xn[-1, :-1]
        wt = dg / 2.0
        wl = jnp.diag(X) / 2.0
        e_row = (wt + wl) + X[t, :]
        okr = ((gnew + sizes) == cspec["omega"]) & (e_row >= cspec["e_floor"])
        dr = jnp.where(okr, e_row, -1.0)
        validc = act & alive & (slot != t)
        dr = jnp.where(validc, dr, -2.0)
        D = D.at[ai, :].set(-2.0).at[:, ai].set(-2.0)
        D = D.at[aj, :].set(-2.0).at[:, aj].set(-2.0)
        D = D.at[t, :].set(dr).at[:, t].set(dr).at[t, t].set(-2.0)
        return (X, D, of2, sizes, act, alive, t + 1, n_act - 1)

    _, _, of2, _, _, alive, tail, _ = jax.lax.while_loop(
        cond, body, (X, D, of2, sizes, act, alive, tail0, n_act0))

    # host output order: cand-universe groups first (act survivors and
    # untouched non-act cand in INPUT position, merged appended in
    # creation order), rest groups after, both ascending.  Keys over the
    # extended id space [0, scap+n): original merge slot -> its n-slot,
    # merged tail slot ms -> n+ms, parked non-act -> n-slot (cand) or
    # n+scap+slot (rest); distinct groups never collide.
    ms = jnp.arange(scap, dtype=jnp.int32)
    key_m = jnp.where(
        ms < n_act0, slot_of_m, (n + ms).astype(jnp.int32))
    key_p = jnp.where(
        is_rest, (n + scap) + jnp.arange(n, dtype=jnp.int32),
        jnp.arange(n, dtype=jnp.int32))
    keys = jnp.concatenate([key_m, key_p])           # (scap + n,)
    return _dense_rank(keys[of2]), tail - tail0


def _install_partition_device(carry, of_new, now, dt, *, n, seed_new):
    """``install_partition`` as segment reductions over the slot maps.

    Matching (``match_partitions``): a new slot matches iff all its
    members came from ONE old slot of the same member count.  Changed
    slots take the member-wise expiry min (fresh iff still beyond
    ``now``), else Alg.-1 window seeding on the seed-count argmax
    server.  The whole (n+1)-row state is rebuilt, which also clears
    any scatter garbage accumulated on the dump row.
    """
    E_old = carry["E"]
    a_old = carry["anchor"]
    of_old = carry["of"]
    cnt_old = carry["cnt"]
    one = jnp.ones(n, jnp.float64)
    cnt_new = jnp.zeros(n + 1, jnp.float64).at[of_new].add(one)
    slot_valid = cnt_new > 0.0
    mn = jax.ops.segment_min(of_old, of_new, num_segments=n + 1)
    mx = jax.ops.segment_max(of_old, of_new, num_segments=n + 1)
    cand = jnp.clip(mn, 0, n)
    matched = slot_valid & (mn == mx) & (cnt_old[cand] == cnt_new)
    item_E = E_old[of_old]                           # (n, m)
    min_E = jax.ops.segment_min(item_E, of_new, num_segments=n + 1)
    fresh = jnp.where(slot_valid[:, None] & (min_E > now), min_E, 0.0)
    row_max = fresh.max(axis=1)
    anew = jnp.where(
        row_max > 0.0, jnp.argmax(fresh, axis=1).astype(jnp.int32), -1)
    if seed_new:
        ssum = jax.ops.segment_sum(
            carry["seed"][:n], of_new, num_segments=n + 1)
        js = jnp.argmax(ssum, axis=1).astype(jnp.int32)
        need = (slot_valid & ~matched & (row_max <= 0.0)
                & (cnt_new > 1.0))
        col = jax.lax.broadcasted_iota(jnp.int32, fresh.shape, 1)
        fresh = jnp.where(
            need[:, None] & (col == js[:, None]),
            now + dt[js][:, None], fresh)
        anew = jnp.where(need, js, anew)
    E_new = jnp.where(matched[:, None], E_old[cand], fresh)
    a_new = jnp.where(matched, a_old[cand], anew)
    return E_new, a_new, cnt_new


def _cgm_boundary(carry, now, cspec, dt, item_sizes, *, n, m, h, wcap,
                  uses_sizes, enable_split, enable_acm, seed_new,
                  use_kernels, gcap, full_merge):
    """One T_CG boundary, fully on device: Alg. 2 -> 4 -> 3 -> install.

    Mirrors ``AKPCPolicy.on_window`` + ``generate_cliques`` + the
    engine's ``install_partition``, then resets the window counters and
    rolls the compact binary CRM + hot index map into the prev-CRM
    carry slots.  All boundary tensors are (h, h) / (scap, scap) —
    nothing n^2 is ever materialised.  Returns the new carry and the
    approximate merge's iteration count (0 without the merge).
    """
    with jax.named_scope("crm"):
        hot_idx, valid_h, lut, raw, norm, binary = _window_crm_device(
            carry, cspec, n=n, h=h, wcap=wcap, use_kernels=use_kernels)
    W = norm.astype(jnp.float64)

    # -- Alg. 4 edge diff vs the previous window, per compact space:
    # removed edges live in the PREV hot space, added edges in the
    # CURRENT one; both index maps ascend in item id, so row-major
    # nonzero order IS the host's lexicographic global edge order
    with jax.named_scope("adjust"):
        p_idx = carry["p_idx"]                       # (h,) prev hot -> item
        pbin = carry["pbin"]
        lut_prev = jnp.full(n + 1, -1, jnp.int32).at[p_idx].set(
            jnp.arange(h, dtype=jnp.int32)).at[n].set(-1)
        ci = lut_prev[hot_idx]                       # cur slot -> prev slot
        pc = lut[p_idx]                              # prev slot -> cur slot
        pcv = pc >= 0
        pcc = jnp.maximum(pc, 0)
        cur_in_prev = binary[pcc][:, pcc] & pcv[:, None] & pcv[None, :]
        civ = ci >= 0
        cic = jnp.maximum(ci, 0)
        prev_in_cur = pbin[cic][:, cic] & civ[:, None] & civ[None, :]
        triu_h = jnp.triu(jnp.ones((h, h), bool), k=1)
        remM = pbin & ~cur_in_prev & triu_h
        addM = binary & ~prev_in_cur & triu_h
        of = carry["of"]
        gsize = carry["cnt"][:n].astype(jnp.int32)
        of, gsize = _adjust_partition(
            of, gsize, binary, W, hot_idx, valid_h, lut,
            addM, remM, p_idx, cspec, n=n, h=h, gcap=gcap)
    if enable_split:
        with jax.named_scope("split"):
            of = _split_oversized(
                of, gsize, W, lut, cspec, n=n, h=h, gcap=gcap)
    merge_iters = jnp.zeros((), jnp.int32)
    if enable_acm:
        with jax.named_scope("merge"):
            of, merge_iters = _approx_merge(
                of, binary, hot_idx, valid_h, cspec, n=n, h=h,
                use_kernels=use_kernels, full_merge=full_merge)

    with jax.named_scope("install"):
        E_new, a_new, cnt_new = _install_partition_device(
            carry, of, now, dt, n=n, seed_new=seed_new)
    out = dict(
        carry, E=E_new, anchor=a_new, of=of, cnt=cnt_new,
        wlen=jnp.zeros((), jnp.int32),
        wcnt=jnp.zeros(n + 1, jnp.int32),
        seed=jnp.zeros((n + 1, m), jnp.int32),
        p_idx=hot_idx, pbin=binary, praw=raw, pnorm=norm,
    )
    if uses_sizes:
        out["vol"] = jnp.zeros(n + 1, jnp.float64).at[of].add(item_sizes)
    return out, merge_iters


# ---------------------------------------------------------------------------
# device: in-scan event construction + the Alg. 5/6 cost step
# ---------------------------------------------------------------------------
def _event_step(carry, x, spec, *, kind, charge, uses_sizes, item_sizes,
                n, m):
    """``batch_events`` + the const-dt replay step, derived in-scan.

    The host dedups (request, clique) keys with ``np.unique`` — sorted
    key order.  Here every (B*d) item slot maps to key ``r*(n+1)+cl``
    (invalid slots -> clique n), a stable argsort groups them, and
    segment sums produce the per-event counts; the event list is the
    host's, interleaved with inert val=False groups (invalid slots and
    request padding) whose writes land on the dump row/col.  The cost
    arithmetic below is copied expression-for-expression from
    ``engine_jax._replay_impl`` (const-dt branch), so the E/anchor
    trajectory stays float-for-float identical and cost sums differ
    only by in-batch summation order (the 1e-9 bar).
    """
    E, anchor, acc = carry["E"], carry["anchor"], carry["acc"]
    of, cnt = carry["of"], carry["cnt"]
    K = n
    items = x["items"]                               # (B, d)
    B, d = items.shape
    NE = B * d
    valid = (items >= 0).reshape(NE)
    item = jnp.clip(items, 0, n - 1).reshape(NE)
    r = jax.lax.broadcasted_iota(jnp.int32, (B, d), 0).reshape(NE)
    cl = jnp.where(valid, of[item], K)
    key = r * (K + 1) + cl
    o = jnp.argsort(key)                             # stable
    sk = key[o]
    first = jnp.concatenate([jnp.ones(1, bool), sk[1:] != sk[:-1]])
    seg = (jnp.cumsum(first.astype(jnp.int32)) - 1).astype(jnp.int32)
    vmask = valid[o]
    n_req_s = jax.ops.segment_sum(
        jnp.where(vmask, 1.0, 0.0), seg, num_segments=NE,
        indices_are_sorted=True)
    if uses_sizes:
        isz = item_sizes[item][o]
        req_size_s = jax.ops.segment_sum(
            jnp.where(vmask, isz, 0.0), seg, num_segments=NE,
            indices_are_sorted=True)
    # compact the unique keys into the event axis; unused tail entries
    # get an inert pad key (last request, dump clique)
    pad_key = (B - 1) * (K + 1) + K
    dst = jnp.where(first, seg, NE)
    ev_key = jnp.full(NE + 1, pad_key, key.dtype).at[dst].set(sk)[:NE]
    ev_r = ev_key // (K + 1)
    ev_c = (ev_key % (K + 1)).astype(jnp.int32)
    ev_j = x["servers"][ev_r]
    ev_t = x["times"][ev_r]
    val = ev_c < K
    n_req = n_req_s
    size = cnt[ev_c]
    if uses_sizes:
        csize = carry["vol"][ev_c]
        req_size = req_size_s
    else:
        csize = size
        req_size = n_req

    # (c, j) view: stable sort keeps ascending request order in-group,
    # exactly the host's o_cj
    key_cj = ev_c * m + ev_j
    o_cj = jnp.argsort(key_cj)
    kcs = key_cj[o_cj]
    first_cj_s = jnp.concatenate([jnp.ones(1, bool), kcs[1:] != kcs[:-1]])
    last_cj_s = jnp.concatenate([kcs[1:] != kcs[:-1], jnp.ones(1, bool)])
    t_cj_s = ev_t[o_cj]
    prev_t_s = jnp.where(
        first_cj_s, 0.0,
        jnp.concatenate([jnp.zeros(1, jnp.float64), t_cj_s[:-1]]))
    first_cj = jnp.zeros(NE, bool).at[o_cj].set(first_cj_s)
    prev_cj_t = jnp.zeros(NE, jnp.float64).at[o_cj].set(prev_t_s)

    # per-clique view (o_c): previous server within the clique group
    o_c = jnp.argsort(ev_c)
    cs = ev_c[o_c]
    first_c_s = jnp.concatenate([jnp.ones(1, bool), cs[1:] != cs[:-1]])
    last_c_s = jnp.concatenate([cs[1:] != cs[:-1], jnp.ones(1, bool)])
    j_c_s = ev_j[o_c]
    prev_j_s = jnp.where(
        first_c_s, -1,
        jnp.concatenate([jnp.full(1, -1, jnp.int32), j_c_s[:-1]]))
    first_c = jnp.zeros(NE, bool).at[o_c].set(first_c_s)
    prev_j = jnp.full(NE, -1, jnp.int32).at[o_c].set(prev_j_s)

    # ---- the replay cost step (engine_jax._replay_impl, const dt) ----
    j, t = ev_j, ev_t
    dt = spec["dt"]
    dt_e = dt[0]
    E_before = jnp.where(first_cj, E[ev_c, j], prev_cj_t + dt_e)
    dep = 0.0 * E_before[0]
    a0 = anchor[ev_c]
    anchor_alive = jnp.where(
        first_c, (a0 == j) & (E_before > 0.0), prev_j == j)
    fresh = E_before > t
    alive = fresh | anchor_alive
    miss = (~alive) & val
    lapsed = alive & (~fresh) & val
    steps = jnp.ceil((t - E_before) / dt_e)
    rr = E_before + steps * dt_e
    rr = jnp.where(rr <= t, rr + dt_e, rr)
    e_eff = jnp.where(fresh, E_before, jnp.where(lapsed, rr, t))
    rate_stored = _rate_hook(kind, spec, size, csize, j)
    rent = jnp.where(lapsed, rate_stored * (e_eff - E_before), 0.0)
    tc = jnp.where(
        miss, _transfer_hook(kind, spec, size, csize, j), 0.0)
    if charge == "requested":
        rate = _rate_hook(kind, spec, n_req, req_size, j)
    else:
        rate = rate_stored
    dur = jnp.maximum((t + dt_e) - jnp.maximum(e_eff, t), 0.0)
    cc = jnp.where(val, rate * dur, 0.0)
    nm = miss.sum()
    acc = acc + jnp.stack([
        tc.sum(), cc.sum(), rent.sum(),
        nm.astype(acc.dtype), (val.sum() - nm).astype(acc.dtype),
        jnp.where(miss, size, 0.0).sum(),
    ])

    # ---- state update on segment-last events (non-lasts -> dump) ----
    uc = jnp.where(last_cj_s, (kcs // m).astype(jnp.int32), K)
    uj = jnp.where(last_cj_s, (kcs % m).astype(jnp.int32), 0)
    E = E.at[uc, uj].set(t_cj_s + dt[0] + dep)
    ac = jnp.where(last_c_s, cs, K)
    a_cur = anchor[ac]
    aE = E[ac, jnp.maximum(a_cur, 0)]                # POST-update E
    t_c_s = ev_t[o_c]
    upd = (a_cur < 0) | (t_c_s + dt[0] >= aE)
    anchor = anchor.at[jnp.where(upd, ac, K)].set(j_c_s)
    return dict(carry, E=E, anchor=anchor, acc=acc)


# ---------------------------------------------------------------------------
# the scan: boundary cond -> window accumulate -> events/costs
# ---------------------------------------------------------------------------
#: times the fused CGM scan body has been TRACED — the device-CGM
#: mirror of ``engine_jax.SCAN_TRACES`` (fresh compiles per new input
#: structure); the live serving engine asserts chunk streams reuse ONE
#: compiled scan (tests/test_serving_live.py)
SCAN_TRACES = 0


def _cgm_replay_impl(spec, cspec, init, xs, item_sizes, *, kind, charge,
                     uses_sizes, enable_split, enable_acm, seed_new,
                     use_kernels, gcap, full_merge):
    global SCAN_TRACES
    SCAN_TRACES += 1
    n = init["of"].shape[0]
    m = init["E"].shape[1]
    h = init["p_idx"].shape[0]
    wcap = init["wbuf"].shape[0]
    dt = spec["dt"]

    def step(carry, x):
        # the boundary fires BEFORE this batch's requests: the step that
        # starts a new T_CG period evaluates the window accumulated by
        # the preceding steps (``x["cg"]`` comes from the shared xs, so
        # under vmap the predicate stays unbatched and cond stays cond)
        with jax.named_scope("cgm_boundary"):
            carry, merge_iters = jax.lax.cond(
                x["cg"],
                lambda c: _cgm_boundary(
                    c, x["now"], cspec, dt, item_sizes, n=n, m=m, h=h,
                    wcap=wcap, uses_sizes=uses_sizes,
                    enable_split=enable_split, enable_acm=enable_acm,
                    seed_new=seed_new, use_kernels=use_kernels, gcap=gcap,
                    full_merge=full_merge),
                lambda c: (c, jnp.zeros((), jnp.int32)),
                carry)
        with jax.named_scope("window_accumulate"):
            carry = _accumulate_window(carry, x, n=n, m=m)
        with jax.named_scope("event_step"):
            carry = _event_step(
                carry, x, spec, kind=kind, charge=charge,
                uses_sizes=uses_sizes, item_sizes=item_sizes, n=n, m=m)
        # per step: the slot map and the boundary's merge iterations
        return carry, (carry["of"], merge_iters)

    return jax.lax.scan(step, init, xs)


@functools.lru_cache(maxsize=64)
def _compiled_cgm_replay(kind, charge, uses_sizes, enable_split,
                         enable_acm, seed_new, use_kernels, gcap,
                         full_merge, vmapped):
    f = functools.partial(
        _cgm_replay_impl, kind=kind, charge=charge,
        uses_sizes=uses_sizes, enable_split=enable_split,
        enable_acm=enable_acm, seed_new=seed_new,
        use_kernels=use_kernels, gcap=gcap, full_merge=full_merge)
    if vmapped:
        # scenarios vmap over spec / cgm spec / carry; the schedule
        # tensors and item sizes are shared unbatched
        f = jax.vmap(f, in_axes=(0, 0, 0, None, None))
    return jax.jit(f)


def kernels_on_backend() -> bool:
    """Whether the boundary step takes the Mosaic CGM kernels: on a TPU
    backend, read at call time.  Elsewhere the static forms tuned for
    XLA (dense one-hot / pair-scatter CRM, edge-scatter X) run instead."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# host seam: carry init, execution, state/policy sync
# ---------------------------------------------------------------------------
def _window_fields(n, m, h, wcap, dbuf) -> dict:
    """The carry fields that an empty window and no previous CRM hold as
    one constant each: name -> (shape, dtype, value)."""
    return {
        "acc": ((N_ACC,), np.float64, 0),
        "wbuf": ((wcap, dbuf), np.int32, -1),
        "wlen": ((), np.int32, 0),
        "wcnt": ((n + 1,), np.int32, 0),
        "seed": ((n + 1, m), np.int32, 0),
        "p_idx": ((h,), np.int32, n),
        "praw": ((h, h), np.float32, 0),
        "pnorm": ((h, h), np.float32, 0),
        "pbin": ((h, h), np.bool_, False),
    }


def init_cgm_carry(state, prev_crm, win_prefix, *, n, m, uses_sizes,
                   item_sizes, layout=None, schedule=None, h=None,
                   wcap=None, dbuf=None):
    """Numpy engine/policy state -> the device scan carry (one lane).

    The carry is ALWAYS dense-n (``of``: n slots, ``E``: (n+1, m)) —
    a StateLayout only has to keep rows unsharded for the in-scan
    segment reductions to see the whole state; bucketed catalogs are
    fine because the carry is built independently of the generic
    schedule geometry.  The compact workspace dims come from the
    ``schedule`` (or explicit ``h`` / ``wcap`` for the live ratchet);
    ``h`` is bumped to fit a restored previous-window CRM.
    """
    from .engine_jax import state_to_device
    from .state_layout import StateLayout

    lay = StateLayout.resolve(layout)
    if not lay.supports_device_cgm(n, m):
        raise ValueError(
            f"device CGM needs row-unsharded state at (n={n}, m={m}); "
            f"{lay.kind!r} shards rows across devices — use the generic "
            "schedule path for this catalog")
    if schedule is not None:
        h = schedule.h if h is None else h
        wcap = schedule.wcap if wcap is None else wcap
        dbuf = schedule.d if dbuf is None else dbuf
    if h is None or wcap is None:
        raise ValueError(
            "init_cgm_carry needs a CGM schedule or explicit h/wcap")
    dbuf = 1 if dbuf is None else int(dbuf)
    prev_nh = int(prev_crm.hot_items.size) if prev_crm is not None else 0
    if prev_nh:
        h = min(n, max(h, _bucket(prev_nh, HOT_BUCKET, HOT_BUCKET)))

    E0, a0 = state_to_device(state, n)
    of0 = np.asarray(state.partition.clique_of, np.int32)
    carry = {
        "E": E0,
        "anchor": a0,
        "of": of0,
        "cnt": np.bincount(of0, minlength=n + 1).astype(np.float64),
        **{k: np.full(shape, v, dt) for k, (shape, dt, v)
           in _window_fields(n, m, h, wcap, dbuf).items()},
    }
    if uses_sizes:
        vol = np.zeros(n + 1, np.float64)
        np.add.at(vol, of0, np.asarray(item_sizes, np.float64))
        carry["vol"] = vol
    if prev_nh:
        # the previous window's CRM in its compact coordinates: hot ids
        # ascend on the host, matching the device's nonzero order
        carry["p_idx"][:prev_nh] = np.asarray(prev_crm.hot_items, np.int32)
        carry["praw"][:prev_nh, :prev_nh] = np.asarray(
            prev_crm.raw, np.float32)
        carry["pnorm"][:prev_nh, :prev_nh] = prev_crm.norm
        carry["pbin"][:prev_nh, :prev_nh] = prev_crm.binary
    if win_prefix is not None:
        p_it, p_sv = win_prefix
        p_it = np.atleast_2d(np.asarray(p_it))
        R0 = int(p_it.shape[0])
        if R0:
            # the open window's already-fed requests (session feed) go
            # straight into the buffer; duplicate-counting item/seed
            # tallies mirror the host window bookkeeping
            if R0 > wcap or p_it.shape[1] > dbuf:
                raise ValueError(
                    f"window prefix ({R0} x {p_it.shape[1]}) exceeds the "
                    f"carry buffer ({wcap} x {dbuf}); build the schedule "
                    "with prefix_rows/prefix_slots")
            carry["wbuf"][:R0, : p_it.shape[1]] = p_it
            carry["wlen"] = np.asarray(R0, np.int32)
            flat = p_it.reshape(-1)
            carry["wcnt"] = np.bincount(
                np.where(flat >= 0, flat, n), minlength=n + 1,
            ).astype(np.int32)
            seed = np.zeros((n + 1, m), np.int64)
            sv = np.repeat(np.asarray(p_sv, np.int64), p_it.shape[1])
            ok = flat >= 0
            np.add.at(seed, (flat[ok], sv[ok]), 1)
            carry["seed"] = seed.astype(np.int32)
    return carry


def fresh_cgm_lanes(lanes: int, *, n, m, uses_sizes, item_sizes, schedule):
    """``lanes`` copies of the carry :func:`init_cgm_carry` gives an empty
    cache over singleton cliques, made on the device: the fields that
    hold one constant as fills, the per-item slot map, clique counts and
    volumes copied once and broadcast over the lane axis.  Returns the
    carry and the bytes copied from the host, which do not grow with
    ``lanes``."""
    h, wcap, dbuf = schedule.h, schedule.wcap, schedule.d
    of = np.arange(n, dtype=np.int32)         # singleton cliques
    per_item = {"of": of,
                "cnt": np.bincount(of, minlength=n + 1).astype(np.float64)}
    if uses_sizes:
        vol = np.zeros(n + 1, np.float64)
        vol[:n] = np.asarray(item_sizes, np.float64)
        per_item["vol"] = vol
    fills = {"E": ((n + 1, m), np.float64, 0),
             "anchor": ((n + 1,), np.int32, -1),
             **_window_fields(n, m, h, wcap, dbuf)}
    with jax.enable_x64(True):
        carry = {k: jnp.full((lanes, *shape), v, dt)
                 for k, (shape, dt, v) in fills.items()}
        carry.update({k: jnp.broadcast_to(jnp.asarray(v), (lanes, *v.shape))
                      for k, v in per_item.items()})
    return carry, sum(v.nbytes for v in per_item.values())


def lane_prunes(cspec) -> np.ndarray:
    """Per lane of ``cspec``: whether its approximate merge prunes to
    groups with a live hot member (omega > 2 and gamma above the
    (omega - 2) / omega density bar), which bounds its act space by h."""
    om = np.atleast_1d(np.asarray(cspec["omega"], np.int64))
    gam = np.atleast_1d(np.asarray(cspec["gamma"], np.float64))
    omf = om.astype(np.float64)
    return (om > 2) & (gam > (omf - 2.0) / omf)


def cgm_loop_statics(cspec, carry0, *, enable_split, enable_acm,
                     cnt_max=None):
    """The two compile-time loop capacities derived from runtime spec.

    * ``gcap`` — member-list width for the split worklist AND the
      adjust-phase group lists: no group can exceed max(initial
      partition, omega) (adjust merges are omega-capped; splits only
      shrink), maxed over vmapped lanes and bucketed to keep recompiles
      rare.  ``cgm_spec`` sets omega = n for no-split lanes, so the
      bound stays an invariant there too.
    * ``full_merge`` — True when ANY lane can run the approximate merge
      OUTSIDE the pruning regime (the w/o-CS ablation: omega = n), so
      the act space must hold all n groups (scap = 2n) instead of 2h.

    ``cnt_max``, the largest clique in ``carry0`` where the caller knows
    it, spares reading a device carry back to the host.
    """
    om = np.atleast_1d(np.asarray(cspec["omega"], np.int64))
    full_merge = bool(enable_acm) and not bool(lane_prunes(cspec).all())
    if cnt_max is None:
        cnt_max = int(np.asarray(carry0["cnt"]).max())
    gcap = _bucket(max(int(om.max()), cnt_max, 2), 8, 8)
    del enable_split
    return gcap, full_merge


def run_cgm_schedule(schedule, spec, statics, cspec, carry0, item_sizes, *,
                     charge="requested", enable_split=True, enable_acm=True,
                     seed_new=True, use_kernels=None, block=True,
                     cnt_max=None):
    """Execute one CGM schedule; returns the final carry, the per-step
    slot maps and the per-step approximate-merge iteration counts (0 off
    the boundary steps).

    ``spec``/``cspec``/``carry0`` may carry a leading scenario axis (the
    fig7 grid); the schedule and item sizes stay shared unbatched.
    ``cnt_max`` is :func:`cgm_loop_statics`'s.
    """
    enable_compile_cache()
    if use_kernels is None:
        use_kernels = kernels_on_backend()
    vmapped = carry0["E"].ndim == 3
    gcap, full_merge = cgm_loop_statics(
        cspec, carry0, enable_split=enable_split, enable_acm=enable_acm,
        cnt_max=cnt_max)
    fn = _compiled_cgm_replay(
        statics, charge, "vol" in carry0, bool(enable_split),
        bool(enable_acm), bool(seed_new), bool(use_kernels), gcap,
        full_merge, vmapped)
    with jax.enable_x64(True):
        spec_j = {k: jnp.asarray(v) for k, v in spec.items()}
        cspec_j = {k: jnp.asarray(v) for k, v in cspec.items()}
        init_j = {k: jnp.asarray(v) for k, v in carry0.items()}
        xs_j = {k: jnp.asarray(v) for k, v in schedule.xs.items()}
        sz_j = (
            jnp.asarray(item_sizes, jnp.float64)
            if item_sizes is not None
            else jnp.ones(schedule.n, jnp.float64))
        final, (ofs, merge_iters) = fn(spec_j, cspec_j, init_j, xs_j, sz_j)
        if not block:
            return final, ofs, merge_iters
        return ({k: np.asarray(v) for k, v in final.items()},
                np.asarray(ofs), np.asarray(merge_iters))


def partition_from_of(n: int, of: np.ndarray) -> CliquePartition:
    """Dense device slot map -> host partition; slot order IS group order,
    so ``result.clique_of == of`` element for element."""
    of = np.asarray(of)
    k = int(of.max()) + 1 if of.size else 0
    groups = [tuple(np.nonzero(of == g)[0].tolist()) for g in range(k)]
    return CliquePartition.from_cliques(n, groups)


def sync_policy_from_run(policy, schedule, ofs, final, part) -> None:
    """Fold the device run's window bookkeeping back into the policy, as
    if ``on_window`` had run per boundary on the host."""
    nbd = int(schedule.boundary_steps.size)
    if nbd == 0:
        return
    for b in schedule.boundary_steps:
        sizes = np.bincount(np.asarray(ofs[int(b)])).astype(np.int64)
        policy.size_history.append(sizes[sizes > 1])
    policy.n_windows += nbd
    policy._partition = part
    policy._prev_crm = WindowCRM.from_compact(
        final["p_idx"], final["praw"], final["pnorm"], final["pbin"],
        n=schedule.n)


def policy_hot_dims(policy) -> list:
    """The ``(top_frac, of_catalog)`` hot-capacity dims of one policy."""
    cfg = policy.config
    return [(float(cfg.top_frac), cfg.top_frac_of == "catalog")]


def replay_cgm(jeng, policy, trace, *, t_cg, batch_size=None, next_cg0=None,
               win_prefix=None):
    """Device-resident AKPC replay: one host->device transfer, zero host
    clique-generation calls.  Drop-in for ``JaxReplayEngine.replay`` when
    ``wants_device_cgm`` approves the (policy, model, trace) triple."""
    eng = jeng.engine
    uses_sizes = bool(eng.model.uses_sizes)
    item_sizes = eng.env.sizes() if uses_sizes else None
    prefix_rows = prefix_slots = 0
    if win_prefix is not None:
        p_it = np.atleast_2d(np.asarray(win_prefix[0]))
        prefix_rows = int(p_it.shape[0])
        prefix_slots = prefix_rows * max(1, int(p_it.shape[1]))
    schedule = build_cgm_schedule(
        trace, t_cg, uses_sizes=uses_sizes, batch_size=batch_size,
        next_cg0=next_cg0, hot_dims=policy_hot_dims(policy),
        prefix_rows=prefix_rows, prefix_slots=prefix_slots)
    jeng.last_schedule = schedule
    cfg = policy.config
    cspec = cgm_spec(cfg, cfg.params, trace.n)
    carry0 = init_cgm_carry(
        eng.state, getattr(policy, "_prev_crm", None), win_prefix,
        n=trace.n, m=trace.m, uses_sizes=uses_sizes, item_sizes=item_sizes,
        layout=getattr(jeng, "layout", None), schedule=schedule)
    final, ofs, _ = run_cgm_schedule(
        schedule, jeng._spec, jeng._statics, cspec, carry0, item_sizes,
        charge=eng.caching_charge,
        enable_split=cfg.enable_split,
        enable_acm=cfg.enable_approx_merge,
        seed_new=eng.seed_new_cliques)
    nbd = int(schedule.boundary_steps.size)
    part = (eng.state.partition if nbd == 0
            else partition_from_of(trace.n, final["of"]))
    eng.state = CacheState(
        partition=part, E=final["E"][: part.k].copy(),
        anchor=final["anchor"][: part.k].copy(), m=eng.m)
    eng._set_partition_caches(part)
    from .engine_jax import apply_acc

    apply_acc(eng.costs, schedule, final["acc"])
    sync_policy_from_run(policy, schedule, ofs, final, part)
    return eng.costs


def wants_device_cgm(policy, trace, model, wmax=None) -> bool:
    """Eligibility gate for the device-resident CGM path.

    ``REPRO_JAX_CGM`` = ``force`` / ``off`` / ``auto`` (default).  Auto
    requires an unmodified AKPC-family policy (the on-device merge/split
    mirrors ``AKPCPolicy.on_window`` exactly), a uniform keepalive dt
    and no custom CRM hooks.  The CATALOG size no longer gates the path
    — the boundary workspace is sized by the padded hot capacity ``h``
    (window working set x ``top_frac``), so auto admits any catalog
    whose ``h`` stays under ``MAX_DEVICE_CGM_HOT`` and whose window
    request counts keep the f32 co-occurrence counters exact.  A lane
    that runs the approximate merge OUTSIDE the pruning regime (omega =
    2, gamma at or below (omega - 2) / omega, the w/o-CS ablation) needs
    a (2n, 2n) merge space instead of (2h, 2h), so auto admits it while
    n stays under ``MAX_DEVICE_CGM_HOT``: its 2n then stays within the
    bound the pruned space 2h already has.

    ``wmax`` is ``_max_window_requests(trace, policy.t_cg)`` where the
    caller holds it already (a sweep gates many points over one log);
    ``None`` computes it here.
    """
    mode = os.environ.get("REPRO_JAX_CGM", "auto").strip().lower()
    if mode in ("off", "0"):
        return False
    from .akpc import AKPCConfig
    from .policy import AKPCPolicy

    cfg = getattr(policy, "config", None)
    if not isinstance(cfg, AKPCConfig):
        return False
    if not isinstance(policy, AKPCPolicy) \
            or type(policy).on_window is not AKPCPolicy.on_window:
        return False
    t_cg = getattr(policy, "t_cg", None)
    if t_cg is None:
        return False
    if cfg.crm_matmul is not None or cfg.pair_edges is not None:
        return False
    dt = np.asarray(model.dt(), np.float64)
    if dt.size and not (dt == dt[0]).all():
        return False
    if mode in ("force", "1"):
        return True
    if wmax is None:
        wmax = _max_window_requests(trace, t_cg)
    if wmax + NE_TARGET >= _F32_EXACT:
        return False
    d_max = max(1, int(getattr(trace, "d_max", 1)))
    smax = min(trace.n, wmax * d_max)
    if hot_capacity(trace.n, smax, policy_hot_dims(policy)) \
            > MAX_DEVICE_CGM_HOT:
        return False
    if cfg.enable_approx_merge and trace.n > MAX_DEVICE_CGM_HOT \
            and not lane_prunes(cgm_spec(cfg, cfg.params, trace.n))[0]:
        return False
    return True
