"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth)."""
from __future__ import annotations

import jax.numpy as jnp


def crm_ref(H):
    """H (B, n) -> (n, n) fp32 co-occurrence counts with zero diagonal."""
    Hf = H.astype(jnp.float32)
    out = Hf.T @ Hf
    n = out.shape[0]
    return out * (1.0 - jnp.eye(n, dtype=jnp.float32))


def clique_pair_edges_ref(M, A):
    """M (k, n), A (n, n) -> X = M A M^T in fp32."""
    Mf = M.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    return Mf @ Af @ Mf.T


def packed_lookup_ref(table, ids):
    """table (C, omega, d), ids (R,) -> (R, omega, d)."""
    return table[ids]
