"""Unified cache-policy layer: protocol, registry, result type, offline driver.

The paper's algorithms (AKPC and the evaluation baselines of §V.B) used to be
exposed as bespoke ``run_*`` functions with divergent result types
(``CostBreakdown`` vs ``AKPCResult``) that all demanded the full ``Trace`` up
front.  This module redesigns that surface around one abstraction:

* ``CachePolicy`` — the protocol every caching method implements:

  - ``on_window(items, servers, now)``  the clique-generation hook invoked at
    every T_CG boundary with the previous window's requests (Alg. 1 Event 1);
    returns the new :class:`CliquePartition` or ``None`` to keep the current
    one.  Policies without a regeneration loop set ``t_cg = None`` and the
    hook is never called.
  - ``initial_partition(trace)``  optional full-trace-knowledge hook for
    OFFLINE methods (DP_Greedy); online policies return ``None``.
  - ``state_dict()`` / ``load_state_dict()``  snapshotable policy state (the
    previous window's CRM, window counters, ...) for mid-stream
    checkpointing by :class:`repro.core.session.CacheSession`.

* a registry — :func:`register_policy` / :func:`get_policy` /
  :func:`list_policies` — naming the paper's method set: ``akpc`` (plus the
  ablations ``akpc_no_acm`` and ``akpc_base``), ``packcache`` (online
  2-packing), ``dp_greedy`` (offline 2-packing), ``no_packing``.

* ``RunResult`` — one result type subsuming the old split: cost breakdown,
  final clique sizes, per-window size history, window count, clique-gen
  seconds and wall seconds.

* ``run_policy`` — the offline driver (full-``Trace`` batched replay).  The
  streaming driver is ``repro.core.session.CacheSession``; both reproduce the
  same costs (tests/test_policy_session.py).

The legacy ``run_*`` functions in ``akpc.py`` / ``baselines.py`` are thin
shims over this registry and stay cost-for-cost identical.
"""
from __future__ import annotations

import dataclasses
import time as _time
from typing import Any, Callable, Protocol, runtime_checkable

import numpy as np

from .. import obs
from .akpc import AKPCConfig
from .cliques import CliquePartition, generate_cliques
from .cost import CacheEnvironment, CostBreakdown, CostModel, CostParams
from .crm import WindowCRM, build_window_crm
from .engine import CachingCharge, ReplayEngine


# ---------------------------------------------------------------------------
# unified result
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RunResult:
    """What any policy run returns (subsumes CostBreakdown + AKPCResult)."""

    policy: str
    costs: CostBreakdown
    clique_sizes: np.ndarray         # sizes of all cliques, final partition
    size_history: list[np.ndarray]   # per-window non-singleton size arrays
    n_windows: int
    cg_seconds: float                # clique-generation wall time
    wall_seconds: float              # end-to-end replay wall time
    config: Any = None               # the policy's config object (if any)
    #: per-shard dispersion when the point carried a trace-shard axis
    #: (SweepPoint with a sequence of traces): {"n", "totals", "mean",
    #: "std", "ci95"} over the per-shard total costs; None otherwise
    shard_stats: dict | None = None

    @property
    def total(self) -> float:
        return self.costs.total

    @property
    def transfer(self) -> float:
        return self.costs.transfer

    @property
    def caching(self) -> float:
        return self.costs.caching

    def as_dict(self) -> dict:
        d = self.costs.as_dict()
        d.update(
            policy=self.policy,
            n_windows=self.n_windows,
            cg_seconds=self.cg_seconds,
            wall_seconds=self.wall_seconds,
        )
        return d


# ---------------------------------------------------------------------------
# protocol
# ---------------------------------------------------------------------------
@runtime_checkable
class CachePolicy(Protocol):
    """Structural protocol implemented by every registered policy."""

    name: str
    params: CostParams
    t_cg: float | None               # regeneration period; None = never

    def bind(self, n: int, m: int) -> None:
        """Reset per-run state for a catalog of n items and m servers."""
        ...

    def on_window(
        self, items: np.ndarray, servers: np.ndarray, now: float
    ) -> CliquePartition | None:
        """Alg. 1 Event 1: mine the window, return the new partition."""
        ...


class BasePolicy:
    """Shared plumbing: window bookkeeping + snapshotable state.

    Subclasses set ``name``/``t_cg`` and implement ``on_window`` (calling
    :meth:`_record` with the produced partition) and, for offline methods,
    :meth:`initial_partition`.
    """

    name = "base"
    t_cg: float | None = None
    caching_charge: CachingCharge = "requested"
    seed_new_cliques: bool = True
    batch_size: int | None = None
    config: Any = None

    def __init__(
        self,
        params: CostParams | None = None,
        env: CacheEnvironment | None = None,
        cost_model: str | CostModel = "table1",
    ):
        if params is None:
            params = env.params if env is not None else CostParams()
        self.params = params
        self.env = env                  # None = derive from the trace/catalog
        self.cost_model = cost_model
        self.bind(0, 0)

    # -- lifecycle ---------------------------------------------------------
    def bind(self, n: int, m: int) -> None:
        self.n = n
        self.m = m
        self._partition: CliquePartition | None = None
        self.size_history: list[np.ndarray] = []
        self.n_windows = 0
        self.cg_seconds = 0.0

    # -- hooks -------------------------------------------------------------
    def initial_partition(self, trace=None) -> CliquePartition | None:
        return None

    def on_window(
        self, items: np.ndarray, servers: np.ndarray, now: float
    ) -> CliquePartition | None:
        return None

    def _record(self, part: CliquePartition, seconds: float) -> None:
        self._partition = part
        self.cg_seconds += seconds
        self.n_windows += 1
        sizes = part.sizes()
        self.size_history.append(sizes[sizes > 1])

    # -- snapshot ----------------------------------------------------------
    def state_dict(self) -> dict:
        """Pure-numpy pytree of the policy's mutable state."""
        hist = self.size_history
        return {
            "n_windows": np.int64(self.n_windows),
            "cg_seconds": np.float64(self.cg_seconds),
            "size_hist": (
                np.concatenate(hist).astype(np.int64)
                if hist else np.zeros(0, np.int64)
            ),
            "size_hist_lens": np.array([len(a) for a in hist], np.int64),
        }

    def load_state_dict(
        self, state: dict, partition: CliquePartition | None = None
    ) -> None:
        self.n_windows = int(state["n_windows"])
        self.cg_seconds = float(state["cg_seconds"])
        flat = np.asarray(state["size_hist"])
        lens = np.asarray(state["size_hist_lens"]).astype(np.int64)
        self.size_history = [
            a.astype(np.int32) for a in np.split(flat, np.cumsum(lens)[:-1])
        ] if lens.size else []
        if partition is not None:
            self._partition = partition


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
_REGISTRY: dict[str, Callable[..., CachePolicy]] = {}


def register_policy(name: str, *aliases: str):
    """Register a policy factory (usable as a class decorator)."""

    def deco(factory):
        for nm in (name, *aliases):
            if nm in _REGISTRY:
                raise ValueError(f"policy {nm!r} already registered")
            _REGISTRY[nm] = factory
        return factory

    return deco


def get_policy(name: str, **kwargs) -> CachePolicy:
    """Instantiate a registered policy by name (fresh state every call)."""
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown policy {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None
    return factory(**kwargs)


def list_policies() -> list[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# pairwise matching shared by PackCache / DP_Greedy (moved from baselines.py)
# ---------------------------------------------------------------------------
def greedy_pair_matching(
    items: np.ndarray, n: int, theta: float, top_frac: float,
    top_frac_of: str = "window",
) -> CliquePartition:
    """Greedy max-weight matching of items into disjoint pairs.

    Edges come from the binary CRM of ``items`` (same Alg.-2 machinery the
    proposed method uses), weights from the normalised CRM; items left
    unmatched stay singletons.
    """
    crm = build_window_crm(items, n, theta, top_frac, top_frac_of=top_frac_of)
    w = np.where(crm.binary, crm.norm, 0.0)
    iu, iv = np.nonzero(np.triu(w, k=1))
    order = np.argsort(-w[iu, iv], kind="stable")
    used = np.zeros(crm.n_hot, dtype=bool)
    pairs: list[tuple[int, ...]] = []
    for e in order:
        a, b = int(iu[e]), int(iv[e])
        if used[a] or used[b]:
            continue
        used[a] = used[b] = True
        pairs.append((int(crm.hot_items[a]), int(crm.hot_items[b])))
    return CliquePartition.from_cliques(n, pairs)


# ---------------------------------------------------------------------------
# the paper's method set as registered policies
# ---------------------------------------------------------------------------
@register_policy("no_packing")
class NoPackingPolicy(BasePolicy):
    """Wang et al. [6]-style online TTL caching: no packing component."""

    name = "no_packing"
    t_cg = None

    def __init__(
        self,
        params: CostParams | None = None,
        caching_charge: CachingCharge = "requested",
        batch_size: int | None = None,
        env: CacheEnvironment | None = None,
        cost_model: str | CostModel = "table1",
    ):
        super().__init__(params, env=env, cost_model=cost_model)
        self.caching_charge = caching_charge
        self.batch_size = batch_size


@register_policy("ttl")
class TTLKeepOrNotPolicy(BasePolicy):
    """Keep-or-not TTL baseline (Le Scouarnec et al., arXiv 1312.0499).

    No packing: the partition is always the singleton partition.  At every
    T_CG boundary the previous window's request counts decide, per item,
    whether a cached copy pays for itself over the next window: item i is
    KEPT iff its window demand covers the rent of one copy,
    ``count_i * lam >= keep_factor * mu * t_cg``.  Items voted "nokeep"
    are never cached — every access is a forced miss priced as a plain
    transfer, realised through the engine's keep-or-not mask
    (:meth:`repro.core.engine.ReplayEngine.set_item_keep`), which the
    replay drivers sync via the :meth:`item_keep` hook.

    ``on_window`` always returns a partition (even though it never
    changes): keep-or-not policies must produce an install record at every
    boundary so the device schedule has a row to hang evictions on.
    """

    name = "ttl"

    def __init__(
        self,
        params: CostParams | None = None,
        t_cg: float = 50.0,
        keep_factor: float = 1.0,
        caching_charge: CachingCharge = "requested",
        batch_size: int | None = None,
        env: CacheEnvironment | None = None,
        cost_model: str | CostModel = "table1",
    ):
        super().__init__(params, env=env, cost_model=cost_model)
        self.t_cg = t_cg
        self.keep_factor = keep_factor
        self.caching_charge = caching_charge
        self.batch_size = batch_size

    def bind(self, n: int, m: int) -> None:
        super().bind(n, m)
        self._keep = np.ones(n, dtype=bool)

    def item_keep(self) -> np.ndarray:
        """Engine keep-or-not hook: the current per-item keep mask."""
        return self._keep

    def on_window(self, items, servers, now):
        del servers, now
        t0 = _time.perf_counter()
        flat = items[items >= 0]
        counts = np.bincount(flat, minlength=self.n).astype(np.float64)
        p = self.params
        self._keep = counts * p.lam >= self.keep_factor * p.mu * self.t_cg
        part = CliquePartition.singletons(self.n)
        self._record(part, _time.perf_counter() - t0)
        return part

    def state_dict(self) -> dict:
        d = super().state_dict()
        d["keep"] = self._keep.copy()
        return d

    def load_state_dict(self, state, partition=None) -> None:
        super().load_state_dict(state, partition)
        if "keep" in state:
            self._keep = np.asarray(state["keep"]).astype(bool).copy()


@register_policy("packcache", "packcache2")
class PackCache2Policy(BasePolicy):
    """Wu et al. [2]: ONLINE pairwise (2-)packing; FP-tree pair mining
    realised as max-weight greedy matching on the window CRM."""

    name = "packcache"

    def __init__(
        self,
        params: CostParams | None = None,
        t_cg: float = 50.0,
        top_frac: float = 0.1,
        top_frac_of: str = "window",
        caching_charge: CachingCharge = "requested",
        batch_size: int | None = None,
        env: CacheEnvironment | None = None,
        cost_model: str | CostModel = "table1",
    ):
        super().__init__(params, env=env, cost_model=cost_model)
        self.t_cg = t_cg
        self.top_frac = top_frac
        self.top_frac_of = top_frac_of
        self.caching_charge = caching_charge
        self.batch_size = batch_size

    def on_window(self, items, servers, now):
        del servers, now
        t0 = _time.perf_counter()
        part = greedy_pair_matching(items, self.n, self.params.theta,
                                    self.top_frac, self.top_frac_of)
        self._record(part, _time.perf_counter() - t0)
        return part


@register_policy("dp_greedy")
class DPGreedyPolicy(BasePolicy):
    """Huang et al. [4]: OFFLINE pairwise packing.  Pairs are matched on the
    CRM of the FULL trace (complete request knowledge) and kept fixed.

    For streaming use without a full trace, pass a precomputed ``partition``
    (e.g. mined from historical traffic)."""

    name = "dp_greedy"
    t_cg = None

    def __init__(
        self,
        params: CostParams | None = None,
        top_frac: float = 0.1,
        top_frac_of: str = "window",
        partition: CliquePartition | None = None,
        caching_charge: CachingCharge = "requested",
        batch_size: int | None = None,
        env: CacheEnvironment | None = None,
        cost_model: str | CostModel = "table1",
    ):
        self._user_partition = partition
        super().__init__(params, env=env, cost_model=cost_model)
        self.top_frac = top_frac
        self.top_frac_of = top_frac_of
        self.caching_charge = caching_charge
        self.batch_size = batch_size

    def bind(self, n: int, m: int) -> None:
        super().bind(n, m)
        self._fixed = self._user_partition

    def initial_partition(self, trace=None) -> CliquePartition | None:
        t0 = _time.perf_counter()
        if self._fixed is None:
            if trace is None:
                raise ValueError(
                    "dp_greedy is offline: construct it with a precomputed "
                    "`partition` or give the session/driver a full trace"
                )
            self._fixed = greedy_pair_matching(
                trace.items, trace.n, self.params.theta, self.top_frac,
                self.top_frac_of,
            )
        self._record(self._fixed, _time.perf_counter() - t0)
        return self._fixed


@register_policy("akpc")
class AKPCPolicy(BasePolicy):
    """Adaptive K-PackCache (the paper's proposed online algorithm, Alg. 1).

    The three ablation variants of Fig. 5/7/9 are registered separately:
    ``akpc`` (split + approximate merge), ``akpc_no_acm`` (split only) and
    ``akpc_base`` (neither; omega unused).
    """

    name = "akpc"

    def __init__(
        self,
        config: AKPCConfig | None = None,
        *,
        params: CostParams | None = None,
        t_cg: float | None = None,
        top_frac: float | None = None,
        top_frac_of: str | None = None,
        split: bool | None = None,
        approx_merge: bool | None = None,
        caching_charge: CachingCharge | None = None,
        seed_new_cliques: bool | None = None,
        batch_size: int | None = None,
        crm_matmul: Callable | None = None,
        pair_edges: Callable | None = None,
        name: str | None = None,
        env: CacheEnvironment | None = None,
        cost_model: str | CostModel = "table1",
    ):
        cfg = config or AKPCConfig()
        if params is None and env is not None:
            if cfg.params == CostParams():
                # a default-params config is "params unset": the env's
                # prices drive the algorithm too
                params = env.params
            elif cfg.params != env.params:
                # a CUSTOMIZED config params must not be silently clobbered
                # (nor silently ignored by the env-priced engine) — same
                # loud contract as ReplayEngine/opt_lower_bound
                raise ValueError(
                    "config.params and env.params disagree; build the "
                    "environment with the config's CostParams (or pass "
                    "params= explicitly)")
        over = {
            "params": params,
            "t_cg": t_cg,
            "top_frac": top_frac,
            "top_frac_of": top_frac_of,
            "enable_split": split,
            "enable_approx_merge": approx_merge,
            "caching_charge": caching_charge,
            "seed_new_cliques": seed_new_cliques,
            "batch_size": batch_size,
            "crm_matmul": crm_matmul,
            "pair_edges": pair_edges,
        }
        cfg = dataclasses.replace(
            cfg, **{k: v for k, v in over.items() if v is not None}
        )
        self.config = cfg
        if name is not None:
            self.name = name
        super().__init__(cfg.params, env=env, cost_model=cost_model)
        self.t_cg = cfg.t_cg
        self.caching_charge = cfg.caching_charge
        self.seed_new_cliques = cfg.seed_new_cliques
        self.batch_size = cfg.batch_size

    def bind(self, n: int, m: int) -> None:
        super().bind(n, m)
        self._prev_crm: WindowCRM | None = None

    # -- Event 1: clique generation on a window of requests ----------------
    def on_window(self, items, servers, now):
        del servers, now
        cfg = self.config
        t0 = _time.perf_counter()
        with obs.span("cgm.window") as sp:
            crm = build_window_crm(
                items, self.n, cfg.params.theta, cfg.top_frac,
                crm_matmul=cfg.crm_matmul,
                top_frac_of=cfg.top_frac_of,
            )
            sp.set_metadata(hot=crm.n_hot)
            omega = cfg.params.omega if cfg.enable_split else self.n
            part = generate_cliques(
                self._partition,
                self._prev_crm,
                crm,
                self.n,
                omega,
                cfg.params.gamma,
                pair_edges=cfg.pair_edges,
                enable_split=cfg.enable_split,
                enable_approx_merge=cfg.enable_approx_merge,
            )
        self._prev_crm = crm
        self._record(part, _time.perf_counter() - t0)
        return part

    # -- snapshot (adds the previous window's CRM) -------------------------
    def state_dict(self) -> dict:
        d = super().state_dict()
        crm = self._prev_crm
        if crm is None:
            d["crm"] = {
                "present": np.int64(0),
                "hot_items": np.zeros(0, np.int32),
                "raw": np.zeros((0, 0), np.int64),
                "norm": np.zeros((0, 0), np.float32),
                "binary": np.zeros((0, 0), bool),
            }
        else:
            d["crm"] = {
                "present": np.int64(1),
                "hot_items": crm.hot_items.copy(),
                "raw": crm.raw.copy(),
                "norm": crm.norm.copy(),
                "binary": crm.binary.copy(),
            }
        return d

    def load_state_dict(self, state, partition=None) -> None:
        super().load_state_dict(state, partition)
        c = state["crm"]
        if int(c["present"]):
            self._prev_crm = WindowCRM(
                hot_items=np.asarray(c["hot_items"]).astype(np.int32),
                raw=np.asarray(c["raw"]).astype(np.int64),
                norm=np.asarray(c["norm"]).astype(np.float32),
                binary=np.asarray(c["binary"]).astype(bool),
            )
        else:
            self._prev_crm = None


register_policy("akpc_no_acm")(
    lambda **kw: AKPCPolicy(
        **{"split": True, "approx_merge": False, "name": "akpc_no_acm", **kw}
    )
)
register_policy("akpc_base")(
    lambda **kw: AKPCPolicy(
        **{"split": False, "approx_merge": False, "name": "akpc_base", **kw}
    )
)


def _learned_factory(**kw):
    # deferred: repro.learned.policy imports this module
    from ..learned.policy import LearnedPolicy

    return LearnedPolicy(**kw)


register_policy("learned")(_learned_factory)


# ---------------------------------------------------------------------------
# offline driver
# ---------------------------------------------------------------------------
def run_policy(
    policy: CachePolicy | str,
    trace,
    *,
    batch_size: int | None = None,
    backend: str = "numpy",
) -> RunResult:
    """Replay a full trace under ``policy`` and return the unified result.

    Equivalent to driving a fresh :class:`~repro.core.session.CacheSession`
    with the whole trace, but runs through ``ReplayEngine.replay`` directly
    so the legacy ``run_*`` shims stay bit-identical to their pre-registry
    behaviour.

    ``backend="jax"`` swaps the replay core for the device-resident
    jit/scan engine (``repro.core.engine_jax``) — same RunResult, costs
    equal at 1e-9 (tests/test_sweep.py); grids of runs are faster still
    through :class:`repro.core.sweep.SweepEngine`.
    """
    if backend == "jax":
        from .engine_jax import run_policy_jax

        return run_policy_jax(policy, trace, batch_size=batch_size)
    if backend != "numpy":
        raise ValueError(f"unknown replay backend {backend!r}")
    if isinstance(policy, str):
        policy = get_policy(policy)
    t0 = _time.perf_counter()
    policy.bind(trace.n, trace.m)
    env = CacheEnvironment.resolve(
        getattr(policy, "env", None), trace, policy.params)
    eng = ReplayEngine(
        trace.n,
        trace.m,
        policy.params,
        caching_charge=getattr(policy, "caching_charge", "requested"),
        seed_new_cliques=getattr(policy, "seed_new_cliques", True),
        env=env,
        cost_model=getattr(policy, "cost_model", "table1"),
    )
    part0 = (
        policy.initial_partition(trace)
        if hasattr(policy, "initial_partition") else None
    )
    if part0 is not None:
        eng.install_partition(part0, now=0.0)
    gen = policy.on_window if policy.t_cg is not None else None
    bs = batch_size if batch_size is not None else getattr(policy, "batch_size", None)
    eng.replay(trace, clique_generator=gen, t_cg=policy.t_cg, batch_size=bs)
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
