"""Vmapped multi-scenario sweep engine over the JAX replay backend.

The paper's headline results (Figs. 5-10) are cost curves swept over
hyperparameter x cost-model x trace grids; PR 1-4 replayed every grid
point serially.  :class:`SweepEngine` makes the SCENARIO the batch axis:

1. every grid point is a :class:`SweepPoint` (policy + trace + pricing
   scenario);
2. points that share (trace, clique-generation hyperparameters, batch
   size) share ONE host-built :class:`~repro.core.engine_jax.ReplaySchedule`
   — an alpha sweep runs clique generation once, not once per alpha,
   because the partition trajectory is a pure function of the trace and
   the CGM knobs (never of prices or cache state, DESIGN.md §10).  The
   trace part of that key is the trace's CONTENT (:func:`_trace_key`), so
   points that each wrap the same log in their own ``Trace`` share too;
   sharing lasts one ``run`` call (no schedule outlives it);
3. scenarios sharing a schedule are stacked along a leading axis (cost
   spec + initial state) and replayed by ONE ``jax.vmap``'d call of the
   compiled scan, with the schedule's event tensors shared UNBATCHED
   across the lanes (``in_axes=None`` — no per-scenario copies);
4. each point comes back as the same :class:`~repro.core.policy.RunResult`
   the serial ``run_policy`` driver returns, cost-for-cost at 1e-9
   (tests/test_sweep.py).

``backend="numpy"`` degrades to the serial per-point loop (the honest
baseline ``benchmarks/sweep_bench.py`` times against, and the fallback
for cost models the JAX backend cannot express).  ``mesh=`` optionally
shards the scenario axis of each stacked group over a device mesh
(``repro.core.state_layout.make_sweep_mesh``) — a no-op on single-device
hosts.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time as _time
from typing import Any, Sequence

import numpy as np

from .. import obs
from .cost import CacheEnvironment, get_cost_model
from .policy import RunResult, get_policy, run_policy
from .state_layout import StateLayout

#: registry policies whose clique-generation trajectory is fully determined
#: by (trace, t_cg, top_frac, top_frac_of, theta, gamma, omega, split/merge
#: flags) — the key under which SweepEngine shares schedules.  Unknown /
#: custom policies always get a private schedule.
SHAREABLE_POLICIES = (
    "no_packing", "packcache", "dp_greedy",
    "akpc", "akpc_no_acm", "akpc_base",
)


@dataclasses.dataclass
class SweepPoint:
    """One grid point: a registered policy replayed over one scenario.

    ``policy_kwargs`` are passed to :func:`~repro.core.policy.get_policy`
    verbatim (``params``, ``t_cg``, ``top_frac``, ``env``, ``cost_model``,
    ...); ``tag`` is an arbitrary caller label carried through to the
    result order (results come back in input order regardless).

    ``trace`` may also be a SEQUENCE of traces — the trace-shard axis:
    shards of one long trace, or per-seed replicas of one workload.  The
    point then replays every shard as an extra vmap lane of the same
    device call (schedules stacked batched, ``engine_jax.run_schedules``)
    and comes back as ONE :class:`~repro.core.policy.RunResult` with the
    per-shard :class:`~repro.core.cost.CostBreakdown`s merged and
    ``shard_stats`` carrying the mean +- CI of the per-shard totals —
    dispersion estimates at near-zero marginal device cost.  All shards
    must share the catalog/server shape ``(n, m)``.
    """

    policy: str
    trace: Any
    policy_kwargs: dict = dataclasses.field(default_factory=dict)
    batch_size: int | None = None
    tag: str = ""


def _shards_of(trace) -> tuple | None:
    """The shard tuple of a sharded ``SweepPoint.trace`` (else None)."""
    if isinstance(trace, (list, tuple)):
        shards = tuple(trace)
        if not shards:
            raise ValueError("SweepPoint.trace sequence is empty")
        n, m = shards[0].n, shards[0].m
        for tr in shards[1:]:
            if tr.n != n or tr.m != m:
                raise ValueError(
                    "trace shards must share the catalog/server shape "
                    f"(n, m): got ({n}, {m}) vs ({tr.n}, {tr.m})")
        return shards
    return None


def _shard_stats(totals: list) -> dict:
    """mean +- 95% CI (normal approx) of the per-shard total costs."""
    a = np.asarray(totals, np.float64)
    std = float(a.std(ddof=1)) if a.size > 1 else 0.0
    return {
        "n": int(a.size),
        "totals": [float(t) for t in totals],
        "mean": float(a.mean()),
        "std": std,
        "ci95": 1.96 * std / float(np.sqrt(a.size)),
    }


def _merge_shard_results(subs: list) -> RunResult:
    """Fold per-shard RunResults into one (the numpy-backend shard path)."""
    merged = dataclasses.replace(subs[0].costs)
    for r in subs[1:]:
        merged.merge(r.costs)
    return dataclasses.replace(
        subs[0], costs=merged,
        cg_seconds=sum(r.cg_seconds for r in subs),
        wall_seconds=sum(r.wall_seconds for r in subs),
        shard_stats=_shard_stats([r.costs.total for r in subs]))


#: across-run cohort shape ratchet: the largest padded dims this process
#: has seen per (n, m, dt-mode, uses-sizes) cohort.  Padding every later
#: schedule of the same cohort up to these dims makes the compiled scan's
#: shapes REPEAT across ``SweepEngine.run`` calls — the jit cache (and the
#: persistent compile cache) hit instead of re-tracing each slightly
#: different grid.  Padded steps/slots are inert, so ratcheting up is
#: semantics-free; a retrace costs ~1s, the extra padding microseconds.
_COHORT_DIMS: dict[tuple, dict] = {}


def _pad_cohort(ej, ckey, recs: list) -> None:
    """Pad the schedules of ``recs`` to their cohort's ratcheted dims."""
    dims_list = [ej.schedule_dims(r["schedule"]) for r in recs]
    dims = {k: max(d[k] for d in dims_list) for k in dims_list[0]}
    cached = _COHORT_DIMS.get(ckey)
    if cached is not None:
        dims = {k: max(dims[k], cached[k]) for k in dims}
    _COHORT_DIMS[ckey] = dims
    for r, d0 in zip(recs, dims_list):
        if d0 != dims:   # shared shapes: skip the pad entirely
            r["schedule"] = ej.pad_schedule(r["schedule"], dims)


def _coarse(x: int) -> int:
    """``x`` rounded up to a sixteenth of its power of two (at most 6.25%
    more).  A device-CGM group's window buffer ``wcap`` tracks the busiest
    window of its log and so moves by a few rows from log to log, while
    the fused scan at a 2,000-item catalog takes minutes to compile: at
    this grain the logs of one deployment share one compiled shape."""
    step = 1 << max(0, x.bit_length() - 5)
    return -(-x // step) * step


def _nbytes(*trees) -> int:
    """Bytes of the arrays in ``trees`` (arrays, or dicts of arrays;
    ``None`` counts nothing)."""
    return sum(int(a.nbytes) for t in trees if t is not None
               for a in (t.values() if isinstance(t, dict) else (t,)))


def _xs_nbytes(schedules) -> int:
    """Bytes of the event tensors of the distinct ``schedules``: lanes
    that replay one schedule copy its tensors to the device once."""
    return _nbytes(*{id(s): s.xs for s in schedules}.values())


def _digest(arrays, memo: dict) -> str:
    """SHA-256 of the bytes of ``arrays`` (``None`` entries skipped),
    memoised on the arrays' identities: ``memo`` lives for one
    ``SweepEngine.run`` call, whose points keep every keyed array alive,
    so an identity cannot be reused within it."""
    ids = tuple(id(a) for a in arrays)
    digest = memo.get(ids)
    if digest is None:
        h = hashlib.sha256()
        for a in arrays:
            if a is not None:
                h.update(np.ascontiguousarray(a))
        digest = memo[ids] = h.hexdigest()
    return digest


def _trace_key(trace, memo: dict) -> tuple:
    """The content of ``trace`` as a schedule-sharing key: equal for equal
    logs, whether or not they are one ``Trace`` object or share arrays.
    Traces over the same arrays cost one digest per call (``memo``)."""
    arrays = (trace.times, trace.servers, trace.items,
              getattr(trace, "sizes", None))
    return (trace.n, trace.m, trace.n_requests, trace.d_max,
            tuple(None if a is None else a.dtype.str for a in arrays),
            _digest(arrays, memo))


def _walks_cgm(policy) -> bool:
    """An AKPC-family policy that generates cliques at window boundaries."""
    return (getattr(policy, "config", None) is not None
            and policy.t_cg is not None)


def _merge_groups(cspecs: list, n: int, h: int, enable_acm: bool) -> list:
    """Split one device-CGM group's lanes by the merge space they
    compile: ``[(positions into cspecs, scap)]``.  A lane that merges
    outside the pruning regime needs ``scap = 2n``; a pruning lane needs
    2h.  Where h is within one hot-capacity bucket of n, every lane stays
    in one program at 2n; otherwise the non-pruning lanes run as a group
    of their own and the pruning lanes keep 2h."""
    from . import cgm_jax

    prunes = cgm_jax.lane_prunes(
        {k: np.stack([np.asarray(c[k]) for c in cspecs]) for k in cspecs[0]})
    lanes = np.arange(len(cspecs))
    if not enable_acm or prunes.all():
        return [(lanes.tolist(), 2 * h)]
    if not prunes.any() or n - h <= cgm_jax.HOT_BUCKET:
        return [(lanes.tolist(), 2 * n)]
    return [(lanes[prunes].tolist(), 2 * h),
            (lanes[~prunes].tolist(), 2 * n)]


#: what ``SweepEngine.stats`` counts of each call: its device-CGM lanes,
#: and the bytes it copies from the host to the device
STATS = ("cgm_lanes", "cgm_host_points", "cgm_hot", "cgm_merge_space",
         "cgm_boundaries", "cgm_window_rows", "cgm_merge_iters",
         "staged_bytes")


def _cgm_key(policy) -> tuple:
    """The clique-generation-relevant knobs of a registry policy."""
    p = policy.params
    cfg = getattr(policy, "config", None)
    if cfg is not None:                     # AKPCPolicy variants
        return (cfg.t_cg, cfg.top_frac, cfg.top_frac_of, cfg.enable_split,
                cfg.enable_approx_merge, cfg.params.theta, cfg.params.gamma,
                cfg.params.omega)
    user_part = getattr(policy, "_user_partition", None)
    return (policy.t_cg, getattr(policy, "top_frac", None),
            getattr(policy, "top_frac_of", None), p.theta,
            None if user_part is None else id(user_part))


class SweepEngine:
    """Replay a grid of scenarios with one vmapped device call per group."""

    def __init__(
        self,
        backend: str = "jax",
        batch_size: int | None = None,
        mesh=None,
        layout: StateLayout | str | None = None,
    ):
        if backend not in ("jax", "numpy"):
            raise ValueError(f"unknown sweep backend {backend!r}")
        self.backend = backend
        self.batch_size = batch_size
        self.mesh = mesh
        layout = StateLayout.resolve(layout)
        if (layout.kind == "row_sharded" and layout.mesh is None
                and mesh is not None
                and layout.row_axis in mesh.axis_names):
            # a bare row_sharded layout adopts the engine's mesh (the
            # make_sweep_mesh(..., state_rows=) two-axis form)
            layout = dataclasses.replace(layout, mesh=mesh)
        self.layout = layout
        #: schedule-dedup stats of the most recent run
        self.last_n_schedules = 0
        #: the most recent call's clique generation, by route: points run
        #: as device-CGM lanes and points of a clique-generating policy
        #: left to a host walk; of the device lanes, the largest padded
        #: hot capacity h, merge space ``scap`` and window buffer
        #: ``wcap``, boundaries x lanes, and approximate-merge iterations
        #: summed over lanes and boundaries (a device counter); and the
        #: bytes the call's ``sweep.stage`` spans copy to the device
        self.stats = dict.fromkeys(STATS, 0)

    # ------------------------------------------------------------------
    def run(self, points: Sequence[SweepPoint]) -> list[RunResult]:
        self.stats = dict.fromkeys(STATS, 0)
        with obs.span("sweep.call", points=len(points)) as sp:
            if self.backend == "numpy":
                self.last_n_schedules = len(points)
                return [self._run_numpy(pt) for pt in points]
            return self._run_jax(points, sp)

    def _run_numpy(self, pt: SweepPoint) -> RunResult:
        shards = _shards_of(pt.trace)
        policy = get_policy(pt.policy, **pt.policy_kwargs)
        self.stats["cgm_host_points"] += _walks_cgm(policy)
        if shards is not None:
            return _merge_shard_results([
                run_policy(
                    get_policy(pt.policy, **pt.policy_kwargs), tr,
                    batch_size=pt.batch_size or self.batch_size)
                for tr in shards])
        return run_policy(
            policy, pt.trace, batch_size=pt.batch_size or self.batch_size)

    # ------------------------------------------------------------------
    def _run_jax(self, points, call_span) -> list[RunResult]:
        from . import cgm_jax
        from . import engine_jax as ej
        from .cliques import CliquePartition
        from .cost import CostBreakdown

        with obs.span("sweep.prepare", points=len(points)) as sp:
            prepared, dev_groups, groups, sh_groups, gate = self._prepare(
                points)
            sp.set_metadata(**gate)
        stats = self.stats
        stats["cgm_host_points"] = sum(
            _walks_cgm(prepared[i]["policy"])
            for idxs in (*groups.values(), *sh_groups.values())
            for i in idxs)

        # -- build every distinct schedule on host --------------------------
        # ``shared`` counts the points served by a schedule built for an
        # earlier point of this call
        schedules: dict = {}
        shared = 0
        for (skey, statics, charge), idxs in groups.items():
            g0 = prepared[idxs[0]]
            if skey in schedules:
                shared += len(idxs)
                continue
            shared += len(idxs) - 1
            policy = g0["policy"]
            with obs.span("sweep.schedule") as sp:
                part0 = (policy.initial_partition(g0["pt"].trace)
                         if hasattr(policy, "initial_partition") else None)
                if part0 is None:
                    part0 = CliquePartition.singletons(g0["pt"].trace.n)
                gen = policy.on_window if policy.t_cg is not None else None
                schedule = ej.build_schedule(
                    part0, g0["pt"].trace, gen, policy.t_cg,
                    model=g0["model"], env=g0["env"], batch_size=g0["bs"],
                    seed_new_cliques=g0["seed"], layout=self.layout,
                )
                sp.set_metadata(steps=schedule.nb, events=schedule.ne)
            schedules[skey] = {
                "schedule": schedule,
                "n_windows": getattr(policy, "n_windows", 0),
                "cg_seconds": getattr(policy, "cg_seconds", 0.0),
                "size_history": list(getattr(policy, "size_history", [])),
                "clique_sizes": schedule.final_partition.sizes(),
            }

        # -- trace-shard groups: one schedule PER SHARD, stacked batched ----
        # lanes = scenarios x shards of one vmapped call (run_schedules);
        # per-shard costs are merged per scenario at collection time.
        sh_pending = []
        n_shard_schedules = 0
        for (skey, statics, charge), idxs in sh_groups.items():
            g0 = prepared[idxs[0]]
            policy = g0["policy"]
            shards = g0["shards"]
            gen = policy.on_window if policy.t_cg is not None else None
            recs = []
            for tr in shards:
                with obs.span("sweep.schedule") as sp:
                    policy.bind(tr.n, tr.m)   # fresh CGM state per shard
                    part0 = (policy.initial_partition(tr)
                             if hasattr(policy, "initial_partition")
                             else None)
                    if part0 is None:
                        part0 = CliquePartition.singletons(tr.n)
                    schedule = ej.build_schedule(
                        part0, tr, gen, policy.t_cg,
                        model=g0["model"], env=g0["env"],
                        batch_size=g0["bs"], seed_new_cliques=g0["seed"],
                        layout=self.layout)
                    sp.set_metadata(steps=schedule.nb, events=schedule.ne)
                recs.append({
                    "schedule": schedule,
                    "n_windows": getattr(policy, "n_windows", 0),
                    "cg_seconds": getattr(policy, "cg_seconds", 0.0),
                    "size_history":
                        list(getattr(policy, "size_history", [])),
                    "clique_sizes": schedule.final_partition.sizes(),
                })
            n_shard_schedules += len(recs)
            shared += len(idxs) - 1
            S_sh = len(recs)
            with obs.span("sweep.stage", lanes=len(idxs) * S_sh) as sp:
                s0 = recs[0]["schedule"]
                ckey = (s0.state_rows, s0.state_cols, s0.const_dt,
                        s0.uses_sizes, "xs")
                _pad_cohort(ej, ckey, recs)
                lanes = [recs[j]["schedule"]
                         for _ in idxs for j in range(S_sh)]
                spec = {
                    k: np.stack([prepared[i]["spec"][k]
                                 for i in idxs for _ in range(S_sh)])
                    for k in g0["spec"]
                }
                staged = _nbytes(spec) + _xs_nbytes(lanes)
                stats["staged_bytes"] += staged
                sp.set_metadata(bytes=staged)
                spec, E0, a0 = self._stage(
                    spec, s0.state_rows, s0.state_cols, len(lanes))
                t0 = _time.perf_counter()
                _, _, acc = ej.run_schedules(
                    lanes, spec, statics, E0, a0, charge=charge,
                    block=False, layout=self.layout)
            sh_pending.append((idxs, recs, acc, t0))

        # -- dispatch device-CGM groups first (non-blocking) ----------------
        # one schedule per group; the lanes dispatch as one program, or
        # two where the group's merge spaces differ (_merge_groups)
        dev_pending = []
        for idxs in dev_groups.values():
            g0 = prepared[idxs[0]]
            trace = g0["pt"].trace
            n, m_srv = trace.n, trace.m
            cfg0 = g0["policy"].config
            uses_sizes = bool(g0["model"].uses_sizes)
            item_sizes = g0["env"].sizes() if uses_sizes else None
            hot_dims = [cgm_jax.policy_hot_dims(prepared[i]["policy"])[0]
                        for i in idxs]
            cspecs = [
                cgm_jax.cgm_spec(prepared[i]["policy"].config,
                                 prepared[i]["policy"].config.params, n)
                for i in idxs
            ]
            with obs.span("sweep.schedule") as sp:
                sched = cgm_jax.build_cgm_schedule(
                    trace, cfg0.t_cg, uses_sizes=uses_sizes,
                    batch_size=g0["bs"], hot_dims=hot_dims)
                # compact-workspace cohort: repeated sweep calls over the
                # same catalog ratchet (nb, B, d, h, W) through
                # _COHORT_DIMS so the CGM scan compiles once per cohort,
                # not once per call shape
                ckey_cgm = ("cgm", n, m_srv, sched.uses_sizes)
                dims = ej.schedule_dims(sched)
                dims["W"] = _coarse(dims["W"])
                cached = _COHORT_DIMS.get(ckey_cgm)
                if cached is not None:
                    dims = {k: max(dims[k], cached[k]) for k in dims}
                _COHORT_DIMS[ckey_cgm] = dims
                sched = ej.pad_schedule(sched, dims)
                subs = _merge_groups(cspecs, n, sched.h,
                                     cfg0.enable_approx_merge)
                scap = max(sc for _, sc in subs)
                sp.set_metadata(steps=sched.nb, events=sched.B * sched.d,
                                h=sched.h, merge_space=scap)
            shared += len(idxs) - 1
            nbd = int(sched.boundary_steps.size)
            stats["cgm_lanes"] += len(idxs)
            stats["cgm_boundaries"] += nbd * len(idxs)
            stats["cgm_hot"] = max(stats["cgm_hot"], sched.h)
            stats["cgm_window_rows"] = max(stats["cgm_window_rows"],
                                           sched.wcap)
            stats["cgm_merge_space"] = max(stats["cgm_merge_space"], scap)
            for sub, _ in subs:
                lane_idx = [idxs[j] for j in sub]
                S = len(sub)
                with obs.span("sweep.stage", lanes=S) as sp:
                    carry0, carry_bytes = cgm_jax.fresh_cgm_lanes(
                        S, n=n, m=m_srv, uses_sizes=uses_sizes,
                        item_sizes=item_sizes, schedule=sched)
                    spec = {
                        k: np.stack([prepared[i]["spec"][k]
                                     for i in lane_idx])
                        for k in g0["spec"]
                    }
                    cspec = {k: np.stack([np.asarray(cspecs[j][k])
                                          for j in sub])
                             for k in cspecs[0]}
                    staged = carry_bytes + _nbytes(spec, cspec, sched.xs,
                                                   item_sizes)
                    stats["staged_bytes"] += staged
                    sp.set_metadata(bytes=staged)
                    t0g = _time.perf_counter()
                    final, ofs, iters = cgm_jax.run_cgm_schedule(
                        sched, spec, g0["statics"], cspec, carry0,
                        item_sizes, charge=g0["charge"],
                        enable_split=cfg0.enable_split,
                        enable_acm=cfg0.enable_approx_merge,
                        seed_new=g0["seed"], block=False, cnt_max=1)
                dev_pending.append((lane_idx, sched, final, ofs, iters, t0g))
        n_dev_schedules = len(dev_groups)

        # -- align schedule shapes so each (n, m, path) cohort compiles the
        # device scan exactly once, then dispatch every group WITHOUT
        # blocking (XLA chews in the background, results collected below)
        pending = []
        with obs.span("sweep.stage") as sp:
            staged = 0
            cohorts: dict = {}
            for rec in schedules.values():
                s = rec["schedule"]
                # cohorts key on the STATE geometry, not the raw (n, m):
                # under a bucketed layout, points whose shapes round to the
                # same bucket land in one cohort and share one compiled scan
                cohorts.setdefault(
                    (s.state_rows, s.state_cols, s.const_dt, s.uses_sizes),
                    []).append(rec)
            for ckey, recs in cohorts.items():
                _pad_cohort(ej, ckey, recs)

            # groups sharing (padded state geometry, statics, charge) stack
            # as lanes of ONE run_schedules call, so a mixed-shape sweep
            # compiles once per bucket COHORT — not once per (schedule,
            # group-width) combination.  Single-group cohorts keep the
            # run_schedule path: one shared schedule vmapped over S specs,
            # no per-lane xs copies.
            cohort_groups: dict = {}
            for (skey, statics, charge), idxs in groups.items():
                s = schedules[skey]["schedule"]
                # the xs key SET is part of the compiled scan's signature
                # (e.g. TTL's "nokeep" mask): only schedules carrying the
                # same event tensors can share one lane-stacked call
                cohort_groups.setdefault(
                    ((s.state_rows, s.state_cols, s.const_dt, s.uses_sizes),
                     frozenset(s.xs), statics, charge),
                    []).append((skey, idxs))

            for (ckey, _xs_keys, statics, charge), members in \
                    cohort_groups.items():
                g0 = prepared[members[0][1][0]]
                if len(members) == 1:
                    skey, idxs = members[0]
                    rec = schedules[skey]
                    schedule = rec["schedule"]
                    S = len(idxs)
                    spec = {
                        k: np.stack([prepared[i]["spec"][k] for i in idxs])
                        for k in g0["spec"]
                    }
                    if S == 1:       # no vmap lane for a singleton group
                        spec = {k: v[0] for k, v in spec.items()}
                    staged += _nbytes(spec, schedule.xs)
                    spec, E0, a0 = self._stage(
                        spec, schedule.state_rows, schedule.state_cols,
                        S if S > 1 else None)
                    t0 = _time.perf_counter()
                    _, _, acc = ej.run_schedule(
                        schedule, spec, statics, E0, a0, charge=charge,
                        block=False, layout=self.layout)
                    pending.append((idxs, [rec] * S, acc, t0))
                    continue
                lane_idx, lanes, lane_recs = [], [], []
                for skey, idxs in members:
                    rec = schedules[skey]
                    for i in idxs:
                        lane_idx.append(i)
                        lanes.append(rec["schedule"])
                        lane_recs.append(rec)
                spec = {
                    k: np.stack([prepared[i]["spec"][k] for i in lane_idx])
                    for k in g0["spec"]
                }
                staged += _nbytes(spec) + _xs_nbytes(lanes)
                spec, E0, a0 = self._stage(
                    spec, lanes[0].state_rows, lanes[0].state_cols,
                    len(lanes))
                t0 = _time.perf_counter()
                _, _, acc = ej.run_schedules(
                    lanes, spec, statics, E0, a0, charge=charge,
                    block=False, layout=self.layout)
                pending.append((lane_idx, lane_recs, acc, t0))
            sp.set_metadata(lanes=sum(len(p[0]) for p in pending),
                            bytes=staged)
            stats["staged_bytes"] += staged
        self.last_n_schedules = (len(schedules) + n_dev_schedules
                                 + n_shard_schedules)
        call_span.set_metadata(
            schedules=self.last_n_schedules,
            shared=shared,
            lanes=len(prepared),
            groups=len(dev_pending) + len(sh_pending) + len(pending),
            cgm_lanes=stats["cgm_lanes"],
            cgm_host=stats["cgm_host_points"])

        # -- collect (blocks on the device results) -------------------------
        with obs.span("sweep.collect"):
            results: list[RunResult | None] = [None] * len(prepared)
            for idxs, sched, final, ofs, iters, t0g in dev_pending:
                with obs.span("sweep.wait"):
                    final = {k: np.asarray(v) for k, v in final.items()}
                    ofs = np.asarray(ofs)
                    stats["cgm_merge_iters"] += int(np.asarray(iters).sum())
                wall = _time.perf_counter() - t0g
                nbd = int(sched.boundary_steps.size)
                for lane, i in enumerate(idxs):
                    pr = prepared[i]
                    costs = CostBreakdown(model=pr["statics"][0])
                    ej.apply_acc(costs, sched, final["acc"][lane])
                    part = cgm_jax.partition_from_of(
                        sched.n, final["of"][lane])
                    hist = []
                    for b in sched.boundary_steps:
                        sz = np.bincount(ofs[lane, int(b)]).astype(np.int64)
                        hist.append(sz[sz > 1])
                    results[i] = RunResult(
                        policy=pr["policy"].name,
                        costs=costs,
                        clique_sizes=part.sizes(),
                        size_history=hist,
                        n_windows=nbd,
                        cg_seconds=0.0,
                        wall_seconds=wall / len(idxs),
                        config=getattr(pr["policy"], "config", None),
                    )
            for idxs, recs, acc, t0 in sh_pending:
                with obs.span("sweep.wait"):
                    acc = np.asarray(acc)
                wall = _time.perf_counter() - t0
                S_sh = len(recs)
                for li, i in enumerate(idxs):
                    pr = prepared[i]
                    merged = CostBreakdown(model=pr["statics"][0])
                    totals = []
                    for j, rec in enumerate(recs):
                        cb = CostBreakdown(model=pr["statics"][0])
                        ej.apply_acc(
                            cb, rec["schedule"], acc[li * S_sh + j])
                        totals.append(cb.total)
                        merged.merge(cb)
                    results[i] = RunResult(
                        policy=pr["policy"].name,
                        costs=merged,
                        clique_sizes=recs[0]["clique_sizes"],
                        size_history=list(recs[0]["size_history"]),
                        n_windows=recs[0]["n_windows"],
                        cg_seconds=sum(r["cg_seconds"] for r in recs),
                        wall_seconds=wall / len(idxs),
                        config=getattr(pr["policy"], "config", None),
                        shard_stats=_shard_stats(totals),
                    )
            for idxs, lane_recs, acc, t0 in pending:
                with obs.span("sweep.wait"):
                    acc = np.atleast_2d(np.asarray(acc))
                wall = _time.perf_counter() - t0
                for lane, i in enumerate(idxs):
                    pr = prepared[i]
                    rec = lane_recs[lane]
                    costs = CostBreakdown(model=pr["statics"][0])
                    ej.apply_acc(costs, rec["schedule"], acc[lane])
                    results[i] = RunResult(
                        policy=pr["policy"].name,
                        costs=costs,
                        clique_sizes=rec["clique_sizes"],
                        size_history=list(rec["size_history"]),
                        n_windows=rec["n_windows"],
                        cg_seconds=rec["cg_seconds"],
                        wall_seconds=wall / len(idxs),
                        config=getattr(pr["policy"], "config", None),
                    )
        return results  # type: ignore[return-value]

    def _prepare(self, points):
        """Per-point policy, environment and cost spec, the groups the
        points fall into (device-CGM super-groups, host-schedule groups
        and trace-shard groups: index lists into the prepared points), and
        the device-CGM gate's counts: ``gated``, the points that reach it
        with a window bound, and ``window_bounds``, the bounds computed."""
        from . import cgm_jax
        from . import engine_jax as ej

        prepared = []
        memo: dict = {}                  # array digests of this call
        for pt in points:
            shards = _shards_of(pt.trace)
            tr0 = shards[0] if shards is not None else pt.trace
            policy = get_policy(pt.policy, **pt.policy_kwargs)
            policy.bind(tr0.n, tr0.m)
            env = CacheEnvironment.resolve(
                getattr(policy, "env", None), tr0, policy.params)
            model = get_cost_model(
                getattr(policy, "cost_model", "table1"), env)
            spec, statics = ej.cost_spec(model, env)
            dt = spec["dt"]
            const_dt = env.m == 0 or bool((dt == dt[0]).all())
            ncol = self.layout.state_cols(env.m)
            if ncol != env.m:
                # bucketed columns: pad the per-server spec arrays so
                # every point of one column bucket shares a compiled shape
                spec = ej.pad_spec_cols(spec, ncol)
            bs = pt.batch_size or self.batch_size
            seed = getattr(policy, "seed_new_cliques", True)
            sizes_fp = (None if not model.uses_sizes
                        else (_digest((env.item_sizes,), memo)
                              if env.item_sizes is not None else "unit"))
            if pt.policy in SHAREABLE_POLICIES:
                tid = (tuple(_trace_key(tr, memo) for tr in shards)
                       if shards is not None else _trace_key(pt.trace, memo))
                skey = (tid, pt.policy, _cgm_key(policy), bs,
                        const_dt, model.uses_sizes, sizes_fp, seed)
            else:
                tid, skey = None, object()          # never shared
            prepared.append({
                "pt": pt, "policy": policy, "spec": spec, "tid": tid,
                "statics": statics, "skey": skey, "sizes_fp": sizes_fp,
                "model": model, "env": env, "bs": bs, "seed": seed,
                "shards": shards,
                "charge": getattr(policy, "caching_charge", "requested"),
            })

        # -- device-CGM super-groups (DESIGN.md §11): AKPC points that
        # differ ONLY in CGM knobs (the fig7 theta/gamma/omega/top_frac
        # axes, plus any pricing axes) share ONE partition-free schedule
        # and vmap the clique generation itself — zero host CGM calls.
        # A group needs >= 2 distinct CGM keys to beat the host path
        # (with one key the host builds one shared schedule anyway).
        # The gate's window bound depends on the log's times and t_cg
        # alone, so it is computed once per (log content, t_cg) a call.
        dev_groups: dict = {}
        bounds: dict = {}
        gated = 0
        for i, pr in enumerate(prepared):
            pt, policy = pr["pt"], pr["policy"]
            cfg = getattr(policy, "config", None)
            if (pr["shards"] is not None
                    or pt.policy not in SHAREABLE_POLICIES or cfg is None
                    # the fused CGM carry is dense-n on its own, whatever
                    # the session layout — only row-sharded state (which
                    # splits the slot maps across devices) falls back
                    or not self.layout.supports_device_cgm(
                        pt.trace.n, pt.trace.m)):
                continue
            wmax = None
            if policy.t_cg is not None:
                gated += 1
                bkey = (pr["tid"], policy.t_cg)
                wmax = bounds.get(bkey)
                if wmax is None:
                    wmax = bounds[bkey] = cgm_jax._max_window_requests(
                        pt.trace, policy.t_cg)
            if not cgm_jax.wants_device_cgm(
                    policy, pt.trace, pr["model"], wmax=wmax):
                continue
            dkey = (pr["tid"], cfg.t_cg, pr["bs"], pr["statics"],
                    pr["charge"], pr["model"].uses_sizes, pr["sizes_fp"],
                    pr["seed"], cfg.enable_split, cfg.enable_approx_merge)
            dev_groups.setdefault(dkey, []).append(i)
        dev_groups = {
            k: v for k, v in dev_groups.items()
            if len({_cgm_key(prepared[i]["policy"]) for i in v}) >= 2
        }
        on_device = {i for v in dev_groups.values() for i in v}

        groups: dict = {}
        sh_groups: dict = {}
        for i, pr in enumerate(prepared):
            if i in on_device:
                continue
            dst = sh_groups if pr["shards"] is not None else groups
            dst.setdefault((pr["skey"], pr["statics"], pr["charge"]),
                           []).append(i)
        gate = {"gated": gated, "window_bounds": len(bounds)}
        return prepared, dev_groups, groups, sh_groups, gate

    # ------------------------------------------------------------------
    def _stage(self, spec, rows, cols, lanes):
        """The spec of ``lanes`` vmap lanes (``None``: one point, no lane
        axis) and their fresh (E, anchor) state, made on the device.

        With ``self.mesh`` the lanes spread over it: the scenario axis
        over the mesh's first axis (not where it does not divide evenly or
        the mesh axis has one device) and, under a row-sharded layout, the
        STATE ROWS over the mesh's ``state_row`` axis; the two compose on
        a 2-D ``make_sweep_mesh(..., state_rows=)`` mesh.  Otherwise the
        state takes the layout's own placement."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from . import engine_jax as ej

        lead = 0 if lanes is None else 1
        mesh, lay = self.mesh, self.layout
        sc = row = None
        if mesh is not None:
            axis = mesh.axis_names[0]
            n_sc = int(mesh.shape[axis])
            sc = axis if (lead and n_sc > 1 and lanes % n_sc == 0) else None
            row = (lay.row_axis
                   if lay.kind == "row_sharded" and lay.mesh is mesh
                   and lay.row_axis in mesh.axis_names
                   and int(mesh.shape[lay.row_axis]) > 1 else None)
        if sc is None and row is None:
            return (spec, *ej.fresh_state_lanes(
                lanes, rows, cols, lay.state_shardings(lead)))
        pfx = (sc,) * lead
        sh = NamedSharding(mesh, P(*pfx))
        with jax.enable_x64(True):  # keep the f64 spec across the put
            spec = {k: jax.device_put(v, sh) for k, v in spec.items()}
        return (spec, *ej.fresh_state_lanes(
            lanes, rows, cols, (NamedSharding(mesh, P(*pfx, row, None)),
                                NamedSharding(mesh, P(*pfx, row)))))


def sweep_points(
    grid: Sequence[dict],
    backend: str | None = None,
    batch_size: int | None = None,
    mesh=None,
    layout: StateLayout | str | None = None,
) -> list[RunResult]:
    """One-shot convenience: each grid entry is SweepPoint kwargs.

    With ``backend`` unset, picks ``REPRO_SWEEP_BACKEND`` (default jax)
    and takes the serial numpy loop when any point's cost model has no
    JAX formula (same rule as ``benchmarks.common.run_method_grid``)."""
    import os

    pts = [SweepPoint(**g) for g in grid]
    if backend is None:
        backend = os.environ.get("REPRO_SWEEP_BACKEND", "jax")
        if backend == "jax":
            from . import engine_jax

            if not all(
                    pt.policy_kwargs.get("cost_model", "table1")
                    in engine_jax.JAX_COST_MODELS
                    for pt in pts):
                backend = "numpy"
    eng = SweepEngine(backend=backend, batch_size=batch_size, mesh=mesh,
                      layout=layout)
    return eng.run(pts)
