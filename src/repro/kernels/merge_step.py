"""Pallas TPU kernel: initial merge matrix D of the Alg.-3 scan.

The device-resident CGM (``core.cgm_jax``) runs the approximate merge as a
``lax.while_loop`` over a thresholded merge matrix

    D[i, j] = e(i u j)   if |i| + |j| == omega and density(i u j) >= gamma
            = -1.0       otherwise,

patched incrementally (one row/col per merge).  The initial D is the only
O(S^2) dense build of the loop; this kernel assembles it on the VPU from the
pair-edge matrix X = M A M^T (``clique_density.py``) and the group sizes:

    within[i]  = X[i, i] / 2
    e(i u j)   = (within[i] + within[j]) + X[i, j].

D holds union edge counts, not densities.  Every eligible pair shares one
e_max = omega (omega - 1) / 2, so the host's float32 density e / e_max
orders and ties pairs exactly as e does (below 2^23, which the device
path guarantees, distinct counts give distinct quotients), and its
``density >= gamma`` bar is ``e >= e_floor`` for the integer floor
``merge_edge_floor`` computes on the host.  No division runs on the device: a float32 divide inside this
kernel on a TPU v5e gave quotients one ulp off numpy's correctly rounded
ones, and a density one ulp off flips the bar or a tie.
All entries are exact small integers in f32, so kernel, jnp twin and the
host reference agree bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

#: block-index zero as an int32 constant: the kernel is traced inside the
#: x64 replay scan, where a bare ``0`` in an index map becomes an i64
#: that Mosaic cannot return next to the i32 program id
_Z = np.int32(0)

#: f32 integers are exact below this; union edge counts stay under it
_F32_EXACT = 2.0 ** 24


def merge_edge_floor(omega: int, gamma: float) -> np.float32:
    """Smallest union edge count whose host density passes ``gamma``.

    The host keeps a pair iff ``float32(e) / float32(e_max) >= float32(
    gamma)`` (``core.cliques._densities``).  A correctly rounded quotient
    is monotone in e, so that test is ``e >= floor``; the floor is found
    with the host's own float32 arithmetic around ceil(gamma e_max).
    """
    em = np.float32(omega * (omega - 1) / 2.0)
    g = np.float32(gamma)
    if em <= 0:
        # omega <= 1: no pair of non-empty groups has |i| + |j| == omega
        return np.float32(0.0)
    e = min(max(np.ceil(float(g) * float(em)), 0.0), _F32_EXACT)
    while e > 0 and np.float32(e - 1.0) / em >= g:
        e -= 1.0
    while e < _F32_EXACT and np.float32(e) / em < g:
        e += 1.0
    return np.float32(e)


def _merge_density_kernel(
    x_ref, wrow_ref, wcol_ref, srow_ref, scol_ref, om_ref, ef_ref,
    out_ref, *, bm: int,
):
    """Grid (Sp/bm,): one row block of D per step, all-pairs elementwise."""
    i = pl.program_id(0)
    x = x_ref[...]                                   # (bm, Sp)
    wi = wcol_ref[...]                               # (bm, 1)
    wj = wrow_ref[...]                               # (1, Sp)
    si = scol_ref[...]                               # (bm, 1) int32
    sj = srow_ref[...]                               # (1, Sp) int32
    om = om_ref[0, 0]
    ef = ef_ref[0, 0]
    r = i * bm + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    e_u = (wi + wj) + x
    keep = ((si + sj) == om) & (r != c) & (e_u >= ef)
    out_ref[...] = jnp.where(keep, e_u, -1.0)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def merge_density(X, sizes, omega, e_floor, *, bm: int = 128,
                  interpret: bool = False):
    """X (S, S) fp32 pair edges, sizes (S,) int32 -> D (S, S) fp32.

    ``omega`` (int32) and ``e_floor`` (float32, ``merge_edge_floor``) are
    runtime scalars so a vmapped hyperparameter sweep can trace this once.
    Pad rows/cols have size 0 and can never pass the ``|i| + |j| ==
    omega`` gate (omega >= 2).
    """
    S = X.shape[0]
    assert X.shape == (S, S) and sizes.shape == (S,)
    Sp = -(-S // max(bm, 128)) * max(bm, 128)
    Xp = jnp.zeros((Sp, Sp), jnp.float32).at[:S, :S].set(X)
    within = jnp.zeros(Sp, jnp.float32).at[:S].set(
        jnp.diag(X).astype(jnp.float32) / 2.0)
    sz = jnp.zeros(Sp, jnp.int32).at[:S].set(sizes.astype(jnp.int32))
    om = jnp.asarray(omega, jnp.int32).reshape(1, 1)
    ef = jnp.asarray(e_floor, jnp.float32).reshape(1, 1)
    out = pl.pallas_call(
        functools.partial(_merge_density_kernel, bm=bm),
        grid=(Sp // bm,),
        in_specs=[
            pl.BlockSpec((bm, Sp), lambda i: (i, _Z)),
            pl.BlockSpec((1, Sp), lambda i: (_Z, _Z)),
            pl.BlockSpec((bm, 1), lambda i: (i, _Z)),
            pl.BlockSpec((1, Sp), lambda i: (_Z, _Z)),
            pl.BlockSpec((bm, 1), lambda i: (i, _Z)),
            pl.BlockSpec((1, 1), lambda i: (_Z, _Z)),
            pl.BlockSpec((1, 1), lambda i: (_Z, _Z)),
        ],
        out_specs=pl.BlockSpec((bm, Sp), lambda i: (i, _Z)),
        out_shape=jax.ShapeDtypeStruct((Sp, Sp), jnp.float32),
        interpret=interpret,
    )(
        Xp,
        within.reshape(1, Sp), within.reshape(Sp, 1),
        sz.reshape(1, Sp), sz.reshape(Sp, 1),
        om, ef,
    )
    return out[:S, :S]


@jax.jit
def merge_density_jnp(X, sizes, omega, e_floor):
    """Fused-jnp twin of the Mosaic kernel, bit-identical to it."""
    S = X.shape[0]
    within = jnp.diag(X) / 2.0
    e_u = (within[:, None] + within[None, :]) + X
    okp = ((sizes[:, None] + sizes[None, :])
           == jnp.asarray(omega, jnp.int32)) & ~jnp.eye(S, dtype=bool)
    keep = okp & (e_u >= jnp.asarray(e_floor, jnp.float32))
    return jnp.where(keep, e_u, -1.0)


def merge_density_auto(X, sizes, omega, e_floor, **kw):
    """Mosaic on TPU, fused jnp elsewhere."""
    if jax.default_backend() == "tpu":
        return merge_density(X, sizes, omega, e_floor, **kw)
    return merge_density_jnp(X, sizes, omega, e_floor)
