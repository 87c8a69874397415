"""Pallas TPU kernel: packed-clique gather — the paper's packed transfer,
on-chip.

The paper's economic claim is that delivering a co-accessed bundle as ONE
packed unit costs (1 + (p-1)*alpha)*lam instead of p*lam.  The memory-system
analogue on TPU: items of a clique stored CONTIGUOUSLY in HBM are fetched
with one streaming DMA per clique ((omega*d)-row burst), instead of omega
scattered row gathers — same bytes, 1/omega the DMA descriptors and no
random-access stalls.

``packed_lookup``: table (C, omega, d) packed cliques, ids (R,) ->
(R, omega, d); one grid step per request, the block index map reads the
clique id from SCALAR-PREFETCH (pltpu.PrefetchScalarGridSpec) so the DMA
address is known before the body runs.  It is a payload gather only: the
replay engines map items to cliques with a numpy gather on the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy_kernel(ids_ref, table_ref, out_ref):
    del ids_ref
    out_ref[...] = table_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def packed_lookup(table, ids, *, interpret: bool = False):
    """table (C, omega, d); ids (R,) int32 -> (R, omega, d)."""
    C, omega, d = table.shape
    R = ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R,),
        in_specs=[pl.BlockSpec((1, omega, d), lambda r, ids: (ids[r], 0, 0))],
        out_specs=pl.BlockSpec((1, omega, d), lambda r, ids: (r, 0, 0)),
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, omega, d), table.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), table)
