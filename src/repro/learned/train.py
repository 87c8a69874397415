"""Jit'd training loop: hindsight windows -> AdamW scan -> LearnedParams.

The whole optimisation — AdamW state init, ``cfg.steps`` minibatch steps,
final full-data loss — is ONE jit'd function whose body is a
``lax.scan``, so a ``train_policy`` call costs exactly one traced compile
per fresh problem shape (``TRAIN_TRACES`` counts them, SCAN_TRACES
style, and tests assert the delta stays <= 2).  Example counts are
padded up to a power-of-two bucket so traces of nearby lengths share the
compiled executable.

Minibatches are importance-sampled proportionally to the hindsight cost
delta ``|cost_keep - cost_evict|`` (host rng, seeded — deterministic),
which folds the example weights into the sampling distribution: the scan
loss is a plain mean of BCE-with-logits over the batch, and the
economically irrelevant weight-0 rows (and padding) are simply never
drawn.

Training math runs under ``jax.enable_x64(True)`` with f64 params
(AdamW keeps f32 moments); the returned :class:`LearnedParams` is numpy f64 throughout
and round-trips through :mod:`repro.checkpoint` via
:func:`save_learned_params` / :func:`load_learned_params`.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from ..core.cost import CacheEnvironment, CostParams
from .featurize import FEATURE_NAMES, FEATURE_SCHEMA_VERSION
from .labels import hindsight_windows
from .model import LearnedParams, warm_params

#: cumulative count of traced compiles of the training step function
#: (incremented at TRACE time, inside the jit'd body — the SCAN_TRACES
#: pattern).  ``train_policy`` is budgeted at <= 2 per call.
TRAIN_TRACES = 0


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Knobs for :func:`train_policy` (defaults sized for smoke runs)."""

    steps: int = 200          # minibatch steps in the scan
    batch: int = 256          # examples per step
    lr: float = 3e-2
    weight_decay: float = 1e-4
    clip_norm: float = 1.0
    warmup_frac: float = 0.1  # warmup_steps = warmup_frac * steps
    warmup_floor: float = 0.1  # short runs: don't start at lr ~ 0
    min_lr_frac: float = 0.05
    d: int = 8                # scorer trunk width
    d_ff: int = 16            # scorer trunk hidden width
    seed: int = 0             # init + minibatch sampling
    keep_factor: float = 1.0  # TTL warm-start threshold factor
    pad_bucket: int = 512     # min example-count bucket (rounded up pow2)


def _bucket(n: int, floor: int) -> int:
    b = max(int(floor), 1)
    while b < n:
        b *= 2
    return b


@functools.lru_cache(maxsize=8)
def _trainer(n_pad: int, n_feat: int, steps: int, batch: int, acfg):
    """Compile-cached jit'd trainer for one (shape, AdamW-config) key."""
    import jax
    import jax.numpy as jnp

    from .adamw import adamw_init, adamw_update
    from .model import forward_jnp

    del n_pad, n_feat, steps, batch  # shape key only; shapes ride the args

    def impl(w, mu, sd, X, y, wt, idx):
        global TRAIN_TRACES
        TRAIN_TRACES += 1

        def batch_loss(w, xb, yb):
            s = forward_jnp(w, mu, sd, xb)
            return jnp.mean(jax.nn.softplus(s) - yb * s)

        grad_fn = jax.value_and_grad(batch_loss)
        state = adamw_init(w)

        def step(carry, ib):
            w, st = carry
            loss, g = grad_fn(w, X[ib], y[ib])
            w2, st2, _ = adamw_update(acfg, g, st, w)
            return (w2, st2), loss

        (w_fin, _), losses = jax.lax.scan(step, (w, state), idx)
        s = forward_jnp(w_fin, mu, sd, X)
        final = jnp.sum(wt * (jax.nn.softplus(s) - y * s)) / jnp.maximum(
            jnp.sum(wt), 1e-12)
        return w_fin, losses, final

    return jax.jit(impl)


def train_policy(trace, env: CacheEnvironment | None = None,
                 cfg: TrainConfig | None = None, *, t_cg: float = 50.0,
                 params: CostParams | None = None,
                 cost_model="table1") -> LearnedParams:
    """Hindsight-label ``trace``'s windows and fit the keep/evict scorer.

    Starts from the TTL-equivalent warm init (:func:`model.warm_params`),
    so on degenerate inputs (no windows, or no example with a nonzero
    cost delta) it returns the warm start untouched.
    """
    import jax

    from .adamw import AdamWConfig

    cfg = cfg or TrainConfig()
    params = params or (env.params if env is not None else CostParams())
    env = CacheEnvironment.resolve(env, trace, params)
    X, y, wt = hindsight_windows(trace, env, t_cg, params=params,
                                 cost_model=cost_model)
    lp = warm_params(params.lam, params.mu, t_cg, cfg.keep_factor,
                     seed=cfg.seed, d=cfg.d, d_ff=cfg.d_ff)
    n = X.shape[0]
    w_sum = float(wt.sum())
    if n == 0 or w_sum <= 0.0:
        return lp

    lp.mu = X.mean(axis=0)
    lp.sd = np.maximum(X.std(axis=0), 1e-9)
    n_pad = _bucket(n, cfg.pad_bucket)
    Xp = np.zeros((n_pad, X.shape[1]), np.float64)
    yp = np.zeros(n_pad, np.float64)
    wp = np.zeros(n_pad, np.float64)
    Xp[:n], yp[:n], wp[:n] = X, y, wt

    rng = np.random.default_rng(cfg.seed)
    idx = rng.choice(n, size=(cfg.steps, cfg.batch),
                     p=wt / w_sum).astype(np.int32)
    acfg = AdamWConfig(
        lr=cfg.lr, weight_decay=cfg.weight_decay, clip_norm=cfg.clip_norm,
        warmup_steps=max(int(cfg.warmup_frac * cfg.steps), 1),
        total_steps=cfg.steps, min_lr_frac=cfg.min_lr_frac,
        warmup_floor=cfg.warmup_floor)
    fn = _trainer(n_pad, X.shape[1], cfg.steps, cfg.batch, acfg)
    with jax.enable_x64(True):
        w_fin, _losses, _final = fn(lp.w, lp.mu, lp.sd, Xp, yp, wp, idx)
    lp.w = jax.tree.map(lambda a: np.asarray(a, np.float64), w_fin)
    return lp


def save_learned_params(lp: LearnedParams, directory: str,
                        step: int = 0, meta: dict | None = None) -> str:
    """Persist trained params through :mod:`repro.checkpoint`."""
    from ..checkpoint import save_checkpoint

    m = {"kind": "learned_params", "schema": int(lp.schema),
         "feature_names": list(lp.feature_names)}
    if meta:
        m.update(meta)
    return save_checkpoint(directory, step, lp.tree(), m)


def load_learned_params(directory: str,
                        step: int | None = None) -> LearnedParams:
    """Inverse of :func:`save_learned_params` (newest step by default)."""
    from ..checkpoint import latest_step, load_checkpoint_tree

    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(
                f"no committed checkpoint under {directory!r}")
    tree, meta = load_checkpoint_tree(directory, step)
    lp = LearnedParams.from_tree(tree)
    names = meta.get("feature_names")
    if names is not None:
        lp.feature_names = tuple(names)
    if lp.schema != FEATURE_SCHEMA_VERSION or lp.feature_names != FEATURE_NAMES:
        raise ValueError(
            f"checkpoint schema v{lp.schema} {lp.feature_names} does not "
            f"match featurizer v{FEATURE_SCHEMA_VERSION} {FEATURE_NAMES}")
    return lp
