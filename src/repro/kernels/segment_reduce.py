"""Segmented running max / running argmax as jnp doubling scans.

The JAX replay backend (``core/engine_jax.py``) keeps two inner segment
reductions on device (everything else is hoisted into the host-built replay
schedule, DESIGN.md §10):

* ``seg_running_max_jnp``    — inclusive running maximum within each
  segment of a (clique, server)-sorted event stream; the value at a
  segment's last position is the pair's post-batch expiry
  ``max_e (t_e + dt_{j_e})``.
* ``seg_running_argmax_jnp`` — the same scan carrying the LATEST index
  attaining the maximum (ties -> later event, matching the scalar
  ``touch`` rule's ``>=`` anchor update); this is the Alg.-6 anchor
  resolution over a clique-sorted event stream under per-server dt
  (DESIGN.md §9).

Both are Hillis-Steele doubling scans: log2(L) rounds of shift + select,
with segment ids from a cumulative sum over the start flags.  They stay
jnp on every backend: the values are f64 expiries, and Mosaic refuses
64-bit operands (it also has no lowering for ``cumsum``), so no Pallas
form of these scans can take them.  ``*_ref`` are the numpy oracles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _n_rounds(L: int) -> int:
    r, d = 0, 1
    while d < L:
        r += 1
        d <<= 1
    return r


def _scan_rounds(v, seg, idx, rounds):
    """Shared doubling rounds on (1, L) arrays; idx may be None."""
    L = v.shape[-1]
    d = 1
    for _ in range(rounds):
        vs = jnp.concatenate(
            [jnp.full((1, d), -jnp.inf, v.dtype), v[:, : L - d]], axis=1)
        ss = jnp.concatenate(
            [jnp.full((1, d), -1, seg.dtype), seg[:, : L - d]], axis=1)
        # earlier candidate wins only if STRICTLY greater: ties keep the
        # LATER index (scalar touch's >= anchor update)
        take = (ss == seg) & (vs > v)
        v = jnp.where(take, vs, v)
        if idx is not None:
            is_ = jnp.concatenate(
                [jnp.zeros((1, d), idx.dtype), idx[:, : L - d]], axis=1)
            idx = jnp.where(take, is_, idx)
        d <<= 1
    return v, idx


def seg_running_max_jnp(values, starts):
    """values (L,), starts (L,) bool -> (L,) inclusive per-segment running
    max.  Segments are contiguous runs beginning where ``starts`` is True
    (position 0 must start a segment)."""
    L = values.shape[-1]
    v = values.reshape(1, L)
    seg = jnp.cumsum(starts.reshape(1, L).astype(jnp.int32), axis=1)
    v, _ = _scan_rounds(v, seg, None, _n_rounds(L))
    return v.reshape(L)


def seg_running_argmax_jnp(values, starts):
    """values (L,), starts (L,) bool -> ((L,) running max, (L,) int32
    index of the LATEST position attaining it within the segment)."""
    L = values.shape[-1]
    v = values.reshape(1, L)
    seg = jnp.cumsum(starts.reshape(1, L).astype(jnp.int32), axis=1)
    idx = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    v, idx = _scan_rounds(v, seg, idx, _n_rounds(L))
    return v.reshape(L), idx.reshape(L)


def seg_running_max_ref(values: np.ndarray, starts: np.ndarray) -> np.ndarray:
    """NumPy oracle: per-position inclusive segment running max."""
    out = np.array(values, dtype=np.float64, copy=True)
    for i in range(1, out.shape[0]):
        if not starts[i]:
            out[i] = max(out[i], out[i - 1])
    return out


def seg_running_argmax_ref(
    values: np.ndarray, starts: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """NumPy oracle: running (max, latest argmax) per segment."""
    v = np.array(values, dtype=np.float64, copy=True)
    idx = np.arange(v.shape[0], dtype=np.int64)
    for i in range(1, v.shape[0]):
        if not starts[i] and v[i - 1] > v[i]:   # ties keep the later index
            v[i] = v[i - 1]
            idx[i] = idx[i - 1]
    return v, idx
