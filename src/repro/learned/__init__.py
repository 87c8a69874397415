"""Learned cache-policy subsystem (DESIGN.md §14).

Three layers over the existing caching core:

* :mod:`featurize` — per-window, per-item features (recency, window
  frequency, CRM co-access degree, size, clique size, inter-arrival
  stats) with a frozen schema version, in numpy f64 and pure-``jnp``
  twin implementations;
* :mod:`train` — hindsight-labeled windows (:mod:`labels`) replayed
  through one jit'd AdamW training scan over a small MLP
  (``model.py`` + ``adamw.py``), ``train_policy(trace, env,
  cfg) -> LearnedParams``, checkpointable via :mod:`repro.checkpoint`;
* :mod:`policy` — the ``learned`` keep-or-not :class:`CachePolicy`
  (registered in ``repro.core.policy``) serving the trained scorer
  inside ``on_window`` through every replay backend.
"""
from .featurize import (
    FEATURE_NAMES,
    FEATURE_SCHEMA_VERSION,
    features_jnp,
    features_np,
    init_stats,
    update_stats,
    window_co_degree,
)
from .model import LearnedParams, forward_jnp, forward_np, init_params, warm_params
from .labels import hindsight_windows
from .policy import LearnedPolicy
from .train import (
    TrainConfig,
    load_learned_params,
    save_learned_params,
    train_policy,
)

__all__ = [
    "FEATURE_NAMES",
    "FEATURE_SCHEMA_VERSION",
    "LearnedParams",
    "LearnedPolicy",
    "TrainConfig",
    "features_jnp",
    "features_np",
    "forward_jnp",
    "forward_np",
    "hindsight_windows",
    "init_params",
    "init_stats",
    "load_learned_params",
    "save_learned_params",
    "train_policy",
    "update_stats",
    "warm_params",
    "window_co_degree",
]
