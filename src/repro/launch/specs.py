"""Abstract input builders for every (arch x shape) dry-run cell.

``input_specs(arch, shape, mesh)`` returns ShapeDtypeStructs (weak-type
correct, sharding-annotated, ZERO device allocation) for the step function
of that cell, plus the step builder itself.  This is the single source of
truth used by dryrun.py, the roofline benches and the launch scripts.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import SHAPES, get_config
from ..models.api import build_model
from ..models.config import ModelConfig
from ..optim import AdamWConfig, adamw_init
from . import sharding as shd
from .mesh import dp_size
from .train import choose_accum, make_train_step

# >=100B-class models accumulate gradients in bf16 (halves the largest
# training buffer; §Perf iteration A3 — precision note in EXPERIMENTS.md)
BF16_ACCUM_ARCHS = {"deepseek_v2_236b"}
# 8-bit AdamW (optim/adamw8bit.py) measured a dry-run REGRESSION when
# enabled here: the per-leaf fp32 dequant->update->requant transients
# overlap in XLA's schedule (+5 GB/dev) — §Perf iteration A5 (refuted).
# Sequencing leaf updates / a fused Pallas quantised-Adam kernel is the
# identified follow-up; the module + convergence tests ship regardless.
OPT8_ARCHS: set = set()


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    cfg: ModelConfig
    kind: str                       # train | prefill | decode
    step_fn: Callable               # the function to lower
    args: tuple                     # ShapeDtypeStructs w/ shardings
    donate: tuple = ()
    static: dict = dataclasses.field(default_factory=dict)
    out_shardings: object = None


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _abstract(tree, shardings):
    return jax.tree.map(
        lambda l, s: _sds(l.shape, l.dtype, s), tree, shardings
    )


def _token_batch(cfg: ModelConfig, accum: int, mb: int, S: int, mesh,
                 train: bool):
    """Token/label (+frontend stub) arrays for one microbatch step."""
    shp = (accum, mb) if train else (mb,)
    batch: dict[str, Any] = {}
    if cfg.family == "encdec":
        batch["frames"] = _sds(shp + (S, cfg.d_model), jnp.bfloat16)
        batch["tokens"] = _sds(shp + (S,), jnp.int32)
    elif cfg.vlm is not None:
        n_text = S - cfg.vlm.n_patches
        batch["patches"] = _sds(shp + (cfg.vlm.n_patches, cfg.vlm.d_patch),
                                jnp.bfloat16)
        batch["tokens"] = _sds(shp + (n_text,), jnp.int32)
    else:
        batch["tokens"] = _sds(shp + (S,), jnp.int32)
    if train:
        batch["labels"] = _sds(shp + (batch["tokens"].shape[-1],), jnp.int32)
    shardings = shd.batch_shardings(batch, mesh, leading_accum=train)
    return _abstract(batch, shardings)


def build_cell(arch: str, shape: str, mesh, *, opt_cfg: AdamWConfig | None = None
               ) -> Cell:
    cfg = get_config(arch)
    sh = SHAPES[shape]
    model = build_model(cfg)
    kind = sh["kind"]
    S, B = sh["seq_len"], sh["global_batch"]
    dp = dp_size(mesh)

    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(model.init, key)
    pshard = shd.param_shardings(params_shape, mesh,
                                 serving=(kind == "decode"))
    params_abs = _abstract(params_shape, pshard)

    if kind == "train":
        opt_cfg = opt_cfg or AdamWConfig()
        accum = choose_accum(cfg, S, B, dp)
        mb = max(1, B // accum)
        batch = _token_batch(cfg, accum, mb, S, mesh, train=True)
        opt_8bit = arch in OPT8_ARCHS
        if opt_8bit:
            from ..optim.adamw8bit import adamw8bit_init

            opt_shape = jax.eval_shape(adamw8bit_init, params_shape)
            oshard = shd.opt8_state_shardings(opt_shape, params_shape, mesh)
        else:
            opt_shape = jax.eval_shape(adamw_init, params_shape)
            oshard = shd.opt_state_shardings(opt_shape, params_shape, mesh)
        opt_abs = _abstract(opt_shape, oshard)
        accum_dtype = jnp.bfloat16 if arch in BF16_ACCUM_ARCHS else jnp.float32
        step = make_train_step(model, opt_cfg, mesh=mesh,
                               accum_dtype=accum_dtype, opt_8bit=opt_8bit)
        return Cell(arch, shape, cfg, kind, step,
                    (params_abs, opt_abs, batch), donate=(0, 1),
                    static={"accum": accum, "microbatch": mb},
                    out_shardings=(pshard, oshard, None))

    if kind == "prefill":
        batch = _token_batch(cfg, 1, B, S, mesh, train=False)
        if cfg.family == "encdec":
            step = functools.partial(model.prefill, mesh=mesh, cache_len=S)
        else:
            step = functools.partial(model.prefill, mesh=mesh)
        return Cell(arch, shape, cfg, kind, step, (params_abs, batch))

    # decode: one new token against a cache of length S
    from ..models.common import dtype_of

    cache_shape = jax.eval_shape(
        functools.partial(model.init_cache, B, S, dtype_of(cfg.kv_cache_dtype))
    )
    cshard = shd.cache_shardings(cache_shape, mesh)
    cache_abs = _abstract(cache_shape, cshard)
    dpspec = shd.batch_spec((B, 1), mesh)
    tokens = _sds((B, 1), jnp.int32, NamedSharding(mesh, dpspec))
    pos = _sds((), jnp.int32, NamedSharding(mesh, P()))
    step = functools.partial(model.decode_step, mesh=mesh)
    return Cell(arch, shape, cfg, kind, step,
                (params_abs, cache_abs, tokens, pos), donate=(1,))


def lower_cell(cell: Cell, mesh):
    """jit + lower with the cell's sharding-annotated abstract inputs."""
    kw = {}
    if cell.out_shardings is not None:
        kw["out_shardings"] = cell.out_shardings
    jitted = jax.jit(cell.step_fn, donate_argnums=cell.donate, **kw)
    with jax.set_mesh(mesh):
        return jitted.lower(*cell.args)
