"""Host-CGM hooks around the CGM matmul kernels.

``AKPCConfig(crm_matmul=..., pair_edges=...)`` takes these explicitly;
the host CGM never wires them in on its own, so the numpy reference
stays on the host on every backend.  Each call picks Mosaic on a TPU
backend and the bit-identical fused-jnp twin elsewhere (exact fp32
integer counts), reading ``jax.default_backend()`` at call time.
"""
from __future__ import annotations

import numpy as np

from .clique_density import clique_pair_edges_auto
from .crm_update import crm_update_auto


def crm_matmul(H):
    """Accelerated CRM accumulation hook for repro.core.crm.build_window_crm:
    H (B, n) one-hot -> (n, n) counts (zero diagonal)."""
    return np.asarray(crm_update_auto(H))


def pair_edges(M, A):
    """Accelerated merge-score hook for repro.core.cliques.merge_scores:
    membership (k, h) x binary CRM (h, h) -> (k, k) union edge counts."""
    return np.asarray(clique_pair_edges_auto(M, A))
