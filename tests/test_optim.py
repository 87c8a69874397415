"""AdamW vs numpy reference; schedule."""
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.learned.adamw import (
    AdamWConfig,
    adamw_init,
    adamw_update,
    cosine_schedule,
)


def test_adamw_matches_numpy_reference():
    cfg = AdamWConfig(lr=0.1, b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0,
                      clip_norm=1e9, warmup_steps=0, total_steps=10**9,
                      min_lr_frac=1.0)
    p = {"w": jnp.array([[1.0, -2.0]], jnp.float32)}
    state = adamw_init(p)
    g = {"w": jnp.array([[0.5, 0.25]], jnp.float32)}
    m = v = np.zeros((1, 2))
    w = np.array([[1.0, -2.0]])
    for step in range(1, 4):
        p, state, _ = adamw_update(cfg, g, state, p)
        gn = np.array([[0.5, 0.25]])
        m = 0.9 * m + 0.1 * gn
        v = 0.99 * v + 0.01 * gn**2
        mh = m / (1 - 0.9**step)
        vh = v / (1 - 0.99**step)
        w = w - 0.1 * mh / (np.sqrt(vh) + 1e-8)
    np.testing.assert_allclose(np.asarray(p["w"]), w, rtol=1e-5)


def test_clipping_and_decay():
    cfg = AdamWConfig(lr=0.1, clip_norm=0.1, weight_decay=0.5,
                      warmup_steps=0, total_steps=10**9, min_lr_frac=1.0)
    p = {"w": jnp.ones((4, 4), jnp.float32)}
    state = adamw_init(p)
    g = {"w": jnp.full((4, 4), 100.0, jnp.float32)}
    p2, state, stats = adamw_update(cfg, g, state, p)
    assert float(stats["grad_norm"]) > 0.1          # raw norm reported
    assert np.all(np.isfinite(np.asarray(p2["w"])))
    assert np.all(np.asarray(p2["w"]) < 1.0)        # decay + update applied


def test_cosine_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
    lrs = [float(cosine_schedule(cfg, jnp.array(s))) for s in (0, 5, 10, 55, 100)]
    assert lrs[0] == 0.0 and math.isclose(lrs[1], 0.5)
    assert math.isclose(lrs[2], 1.0)
    assert lrs[3] < 1.0 and math.isclose(lrs[4], 0.1, rel_tol=1e-5)


def test_cosine_schedule_warmup_floor_default_bitwise():
    """warmup_floor=0.0 (the default) must preserve the original ramp
    BITWISE: floor + (1-floor)*ramp literally adds 0.0 and scales by 1.0."""
    cfg = AdamWConfig(lr=0.37, warmup_steps=13, total_steps=100,
                      min_lr_frac=0.1)
    assert cfg.warmup_floor == 0.0

    def old_schedule(step):                  # the pre-floor formula, verbatim
        step = step.astype(jnp.float32)
        warm = step / jnp.maximum(cfg.warmup_steps, 1)
        prog = jnp.clip(
            (step - cfg.warmup_steps)
            / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
        cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (
            1 + jnp.cos(jnp.pi * prog))
        return cfg.lr * jnp.where(step < cfg.warmup_steps, warm, cos)

    for s in range(0, 101, 7):
        step = jnp.array(s)
        assert float(cosine_schedule(cfg, step)) == float(old_schedule(step))


def test_cosine_schedule_warmup_floor_semantics():
    """With a floor f the warmup ramps linearly f*lr -> lr, and the
    post-warmup cosine leg is untouched."""
    f = 0.25
    base = AdamWConfig(lr=2.0, warmup_steps=10, total_steps=100,
                       min_lr_frac=0.1)
    cfg = AdamWConfig(lr=2.0, warmup_steps=10, total_steps=100,
                      min_lr_frac=0.1, warmup_floor=f)
    assert math.isclose(float(cosine_schedule(cfg, jnp.array(0))), f * cfg.lr)
    mid = float(cosine_schedule(cfg, jnp.array(5)))
    assert math.isclose(mid, (f + (1 - f) * 0.5) * cfg.lr, rel_tol=1e-6)
    # floor applies only below warmup_steps
    for s in (10, 55, 100):
        assert float(cosine_schedule(cfg, jnp.array(s))) == float(
            cosine_schedule(base, jnp.array(s)))
