"""Pallas TPU kernel: CRM co-occurrence accumulation (paper Alg. 2).

GPU formulation: scatter-add atomics over item pairs.  TPU adaptation
(DESIGN.md §2): co-occurrence counting is the rank-B update

    CRM += H^T @ H      with H (B, n) the request/item one-hot incidence,

i.e. a matmul — the systolic MXU does it at matmul speed with zero atomics.
The kernel is a transpose-matmul tiled over (n/bm, n/bn) output blocks with a
k-loop over request blocks; fp32 accumulation lives in a VMEM scratch.

Target: TPU v5e (128x128 MXU tiles).  The tests check the kernel body in
interpret mode against ``ref.crm_ref`` (tests/test_kernels.py) and compile
it for a described v5e chip (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _crm_kernel(h1_ref, h2_ref, out_ref, acc_ref, *, n_k: int):
    """Grid (n/bm, n/bn, B/bk): out[i, j] += h1[k, i]^T @ h2[k, j]."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = h1_ref[...].astype(jnp.float32)          # (bk, bm)
    b = h2_ref[...].astype(jnp.float32)          # (bk, bn)
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _store():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def crm_update(H, *, bm: int = 128, bn: int = 128, bk: int = 128,
               interpret: bool = False):
    """H (B, n) 0/1 incidence -> (n, n) fp32 co-occurrence counts, zero diag.

    Pads B and n up to tile multiples (zero rows/cols contribute nothing).
    """
    B, n = H.shape
    Bp = -(-B // bk) * bk
    npad = max(-(-n // bm) * bm, -(-n // bn) * bn)
    Hp = jnp.zeros((Bp, npad), H.dtype).at[:B, :n].set(H)
    n_k = Bp // bk
    out = pl.pallas_call(
        functools.partial(_crm_kernel, n_k=n_k),
        grid=(npad // bm, npad // bn, n_k),
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((npad, npad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(Hp, Hp)
    out = out[:n, :n]
    return out * (1.0 - jnp.eye(n, dtype=jnp.float32))


@jax.jit
def crm_update_jnp(H):
    """Fused-jnp fallback: the same f32 0/1 contraction + zero diagonal.

    Bit-identical to the Mosaic kernel — both accumulate exact small
    integers in fp32 — so ``crm_update_auto`` can switch per backend
    without moving the parity bar.
    """
    Hf = H.astype(jnp.float32)
    out = Hf.T @ Hf
    return out * (1.0 - jnp.eye(H.shape[1], dtype=jnp.float32))


def crm_update_auto(H, **kw):
    """Mosaic on TPU, fused jnp elsewhere (replaces interpret mode: the
    Python-interpreted Pallas body validated logic but was far slower
    than XLA's native matmul on CPU/GPU)."""
    if jax.default_backend() == "tpu":
        return crm_update(H, **kw)
    return crm_update_jnp(H)
