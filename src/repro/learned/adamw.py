"""AdamW from scratch (no optax) for the learned scorer's trainer: fp32
moments, global-norm clipping, decoupled weight decay, cosine schedule
with linear warmup.  Moments are plain pytrees mirroring the params.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    #: lr fraction at step 0 of the warmup ramp: warmup runs linearly from
    #: warmup_floor*lr to lr instead of from 0.  The default 0.0 preserves
    #: the original schedule bitwise (adds 0.0, scales by 1.0); short runs
    #: (e.g. policy training with warmup_steps ~ total_steps/10) set it so
    #: the first steps are not wasted at near-zero lr.
    warmup_floor: float = 0.0


def cosine_schedule(cfg: AdamWConfig, step):
    step = step.astype(jnp.float32)
    warm = cfg.warmup_floor + (1.0 - cfg.warmup_floor) * (
        step / jnp.maximum(cfg.warmup_steps, 1)
    )
    prog = (step - cfg.warmup_steps) / jnp.maximum(
        cfg.total_steps - cfg.warmup_steps, 1
    )
    prog = jnp.clip(prog, 0.0, 1.0)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * jnp.where(step < cfg.warmup_steps, warm, cos)


def adamw_init(params):
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def global_norm(tree):
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))
    )


def adamw_update(cfg: AdamWConfig, grads, state, params):
    """Returns (new_params, new_state, stats)."""
    step = state["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))
    lr = cosine_schedule(cfg, step)
    bc1 = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    def upd(g, m, v, p):
        g = g.astype(jnp.float32) * scale
        m_new = cfg.b1 * m + (1 - cfg.b1) * g
        v_new = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
        mhat = m_new / bc1
        vhat = v_new / bc2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        wd = cfg.weight_decay if p.ndim >= 2 else 0.0   # no decay on norms/bias
        p_new = p.astype(jnp.float32) * (1.0 - lr * wd) - lr * delta
        return p_new.astype(p.dtype), m_new, v_new

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = tdef.flatten_up_to(grads)
    flat_m = tdef.flatten_up_to(state["m"])
    flat_v = tdef.flatten_up_to(state["v"])
    out = [upd(g, m, v, p) for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
    new_p = tdef.unflatten([o[0] for o in out])
    new_m = tdef.unflatten([o[1] for o in out])
    new_v = tdef.unflatten([o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "step": step}, {
        "grad_norm": gnorm, "lr": lr,
    }
