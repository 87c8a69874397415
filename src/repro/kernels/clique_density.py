"""Pallas TPU kernel: all-pairs clique union edge counts  X = M A M^T.

Implements the Alg.-3 approximate-merge scan (paper lines 4-10) in matrix
form: M (k, n) is the 0/1 clique-membership matrix restricted to the hot
items, A (n, n) the binary CRM; then

    X[i, j]   = cross-edge count between cliques i and j   (i != j)
    X[i, i]/2 = within-edge count of clique i

so the union density of every candidate pair is elementwise from X — the
whole O(k^2 w^2) pair scan collapses into two MXU matmuls.

Kernel shape: two passes of one tiled matmul, ``T = M @ A`` then
``X = T @ M^T``, each over a (rows, cols, contraction) grid with an fp32
VMEM accumulator.  Only one tile of each operand is resident at a time,
so VMEM stays bounded whatever h is (the earlier single-pass form held
all of A and M and ran out of VMEM at h = 2048).  The dots run at
``Precision.HIGHEST``: T holds integers up to n, which a single bf16 MXU
pass would round above 256, and X must stay the exact integer count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _tile(d: int) -> int:
    """Largest MXU-friendly tile dividing ``d`` (a multiple of 128)."""
    return next(t for t in (512, 256, 128) if d % t == 0)


def _matmul_kernel(a_ref, b_ref, out_ref, acc_ref, *, n_k: int, dims):
    """Grid (rows, cols, contraction): out[i, j] = sum_k a[i, k] . b[., .]."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (dims, ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        out_ref[...] = acc_ref[...]


def _matmul(a, b, *, transpose_b: bool, interpret: bool):
    """a (r, c) @ b (c, s), or a @ b^T with b (s, c); dims % 128 == 0."""
    r, c = a.shape
    s = b.shape[0] if transpose_b else b.shape[1]
    br, bs, bc = _tile(r), _tile(s), _tile(c)
    if transpose_b:
        b_spec = pl.BlockSpec((bs, bc), lambda i, j, k: (j, k))
        dims = ((1,), (1,))
    else:
        b_spec = pl.BlockSpec((bc, bs), lambda i, j, k: (k, j))
        dims = ((1,), (0,))
    n_k = c // bc
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k, dims=dims),
        grid=(r // br, s // bs, n_k),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j, k: (i, k)), b_spec],
        out_specs=pl.BlockSpec((br, bs), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, s), jnp.float32),
        scratch_shapes=[pltpu.VMEM((br, bs), jnp.float32)],
        interpret=interpret,
    )(a, b)


@functools.partial(jax.jit, static_argnames=("interpret",))
def clique_pair_edges(M, A, *, interpret: bool = False):
    """M (k, n) 0/1 membership, A (n, n) binary CRM -> X (k, k) fp32.

    k and n are padded to multiples of 128; pad rows/cols are zero and
    contribute nothing.
    """
    k, n = M.shape
    assert A.shape == (n, n)
    kp = -(-k // 128) * 128
    np_ = -(-n // 128) * 128
    Mp = jnp.zeros((kp, np_), jnp.float32).at[:k, :n].set(M)
    Ap = jnp.zeros((np_, np_), jnp.float32).at[:n, :n].set(A)
    T = _matmul(Mp, Ap, transpose_b=False, interpret=interpret)
    X = _matmul(T, Mp, transpose_b=True, interpret=interpret)
    return X[:k, :k]


@jax.jit
def clique_pair_edges_jnp(M, A):
    """Fused-jnp fallback: two XLA matmuls, exact fp32 integer counts —
    bit-identical to the Mosaic kernel."""
    Mf = M.astype(jnp.float32)
    return Mf @ A.astype(jnp.float32) @ Mf.T


def clique_pair_edges_auto(M, A, **kw):
    """Mosaic on TPU, fused jnp elsewhere (replaces interpret mode)."""
    if jax.default_backend() == "tpu":
        return clique_pair_edges(M, A, **kw)
    return clique_pair_edges_jnp(M, A)
