"""The learned keep/evict scorer: linear skip + small gated-MLP residual.

    score(x) = z @ w_lin + b + mlp(z @ w_in) @ w_out,   z = (x - mu) / sd

where ``mlp`` is one SwiGLU block (:func:`init_mlp` /
:func:`mlp_forward`).  An item is KEPT iff its score is >= 0.

Two forwards over the same parameter tree:

* :func:`forward_np` — numpy float64, the canonical serving path (the
  policy decides keep/evict with it on host, for every backend);
* :func:`forward_jnp` — the ``jnp`` twin the jit'd trainer
  differentiates through (``mlp_forward`` verbatim).

:func:`warm_params` zeroes the MLP head and sets the linear part to the
TTL break-even rule on the ``log_window_count`` feature — so an
UNTRAINED ``learned`` policy reproduces the TTL baseline's decisions
exactly (tests pin this), and training starts from a sane prior instead
of noise.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .featurize import FEATURE_NAMES, FEATURE_SCHEMA_VERSION

#: trunk activation — SwiGLU
ACTIVATION = "silu"


class KeyGen:
    """Deterministic fresh-key generator for building param trees."""

    def __init__(self, key):
        self._key = key

    def __call__(self):
        import jax

        self._key, sub = jax.random.split(self._key)
        return sub


def dense_init(key, shape, dtype, fan_in: int | None = None):
    import jax
    import jax.numpy as jnp

    fan = fan_in if fan_in is not None else shape[-2] if len(shape) >= 2 else shape[-1]
    scale = 1.0 / np.sqrt(max(fan, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_mlp(kg: KeyGen, d: int, d_ff: int, L: int, dtype, activation: str) -> dict:
    """``L`` stacked gated-MLP layers: ``wi``, ``wo`` and, for SwiGLU, ``wg``."""
    p = {
        "wi": dense_init(kg(), (L, d, d_ff), dtype, fan_in=d),
        "wo": dense_init(kg(), (L, d_ff, d), dtype, fan_in=d_ff),
    }
    if activation == "silu":                      # SwiGLU gate
        p["wg"] = dense_init(kg(), (L, d, d_ff), dtype, fan_in=d)
    return p


def mlp_forward(p, x, activation: str):
    import jax

    h = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[activation](x @ p["wi"])
    if "wg" in p:
        h = h * (x @ p["wg"])
    return h @ p["wo"]


@dataclasses.dataclass
class LearnedParams:
    """Trained scorer: weights + input normalisation + schema tag.

    Everything is a plain numpy-f64 pytree (nested dicts of arrays), so
    the whole object snapshots through ``CacheSession`` checkpoints and
    ``repro.checkpoint`` unchanged.
    """

    schema: int                      # FEATURE_SCHEMA_VERSION at train time
    mu: np.ndarray                   # (F,) feature means
    sd: np.ndarray                   # (F,) feature stds (>= 1e-9)
    w: dict                          # {"w_lin","b","w_in","trunk","w_out"}
    feature_names: tuple = FEATURE_NAMES

    @property
    def n_features(self) -> int:
        return int(self.mu.shape[0])

    def tree(self) -> dict:
        """Checkpointable pure-array pytree (inverse: :meth:`from_tree`)."""
        return {
            "schema": np.int64(self.schema),
            "mu": self.mu,
            "sd": self.sd,
            "w": self.w,
        }

    @classmethod
    def from_tree(cls, tree: dict) -> "LearnedParams":
        w = {k: (dict(v) if isinstance(v, dict) else np.asarray(v, np.float64))
             for k, v in tree["w"].items()}
        if "trunk" in w:
            w["trunk"] = {k: np.asarray(v, np.float64)
                          for k, v in w["trunk"].items()}
        return cls(
            schema=int(tree["schema"]),
            mu=np.asarray(tree["mu"], np.float64),
            sd=np.asarray(tree["sd"], np.float64),
            w=w,
        )


def init_params(seed: int = 0, d: int = 8, d_ff: int = 16,
                n_features: int | None = None) -> LearnedParams:
    """Fresh scorer parameters (zero linear part, ``init_mlp`` trunk).

    The trunk comes from :func:`init_mlp` (one stacked layer,
    SwiGLU); the output head ``w_out`` starts at ZERO so
    a fresh scorer is exactly its linear part — see :func:`warm_params`.
    All leaves are cast to numpy float64 (the serving dtype).
    """
    import jax
    import jax.numpy as jnp

    F = n_features if n_features is not None else len(FEATURE_NAMES)
    kg = KeyGen(jax.random.PRNGKey(seed))
    trunk = init_mlp(kg, d, d_ff, 1, jnp.float32, ACTIVATION)
    w = {
        "w_lin": np.zeros(F, np.float64),
        "b": np.zeros((), np.float64),
        "w_in": np.asarray(dense_init(kg(), (F, d), jnp.float32, fan_in=F),
                           np.float64),
        "trunk": {k: np.asarray(v, np.float64) for k, v in trunk.items()},
        "w_out": np.zeros(d, np.float64),
    }
    return LearnedParams(
        schema=FEATURE_SCHEMA_VERSION,
        mu=np.zeros(F, np.float64),
        sd=np.ones(F, np.float64),
        w=w,
    )


def warm_params(lam: float, mu_price: float, t_cg: float,
                keep_factor: float = 1.0, seed: int = 0, d: int = 8,
                d_ff: int = 16) -> LearnedParams:
    """TTL-equivalent warm start.

    The TTL baseline keeps item i iff ``count_i * lam >= keep_factor *
    mu * t_cg``.  With the zeroed MLP head the scorer is linear in the
    features, and ``log1p`` is strictly monotone, so

        score = log1p(count) - log1p(keep_factor * mu * t_cg / lam)

    has the same sign as the TTL rule.  Training then refines from the
    baseline instead of from noise (and ``w_out`` is the first gradient
    to move, switching the MLP residual on smoothly).
    """
    p = init_params(seed=seed, d=d, d_ff=d_ff)
    thr = keep_factor * mu_price * t_cg / max(lam, 1e-12)
    p.w["w_lin"][0] = 1.0
    p.w["b"] = np.float64(-np.log1p(thr))
    return p


def _silu_np(x: np.ndarray) -> np.ndarray:
    # branch on sign so exp() never sees a large positive argument
    pos = x >= 0
    e = np.exp(np.where(pos, -x, x))
    return np.where(pos, x / (1.0 + e), x * e / (1.0 + e))


def forward_np(params: LearnedParams, x: np.ndarray) -> np.ndarray:
    """(n, F) features -> (n,) scores; numpy f64, the canonical path."""
    if params.schema != FEATURE_SCHEMA_VERSION:
        raise ValueError(
            f"LearnedParams schema {params.schema} != featurizer schema "
            f"{FEATURE_SCHEMA_VERSION}; retrain or pin the older repro")
    w = params.w
    z = (np.asarray(x, np.float64) - params.mu) / params.sd
    h = z @ w["w_in"]
    t = w["trunk"]
    g = _silu_np(h @ t["wi"][0])
    if "wg" in t:
        g = g * (h @ t["wg"][0])
    y = g @ t["wo"][0]
    return z @ w["w_lin"] + w["b"] + y @ w["w_out"]


def forward_jnp(w: dict, mu, sd, x):
    """``jnp`` twin of :func:`forward_np` over the raw weight tree.

    Takes the weight pytree (not the dataclass) so the trainer can
    differentiate through it; the trunk runs through ``mlp_forward``
    verbatim.  Matches the numpy path to f64 round-off under x64.
    """
    z = (x - mu) / sd
    h = z @ w["w_in"]
    t = {k: v[0] for k, v in w["trunk"].items()}
    y = mlp_forward(t, h, ACTIVATION)
    return z @ w["w_lin"] + w["b"] + y @ w["w_out"]
