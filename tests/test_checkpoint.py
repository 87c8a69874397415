"""Checkpoint roundtrip (incl. bf16), commit marker, structure check."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)


def _tree():
    return {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "b": {"w": jnp.ones((4,), jnp.bfloat16) * 1.5,
              "step": jnp.array(7, jnp.int32)},
    }


def test_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 3, t, meta={"note": "x"})
    restored, meta = restore_checkpoint(str(tmp_path), 3, t)
    assert meta["note"] == "x"
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_commit_marker_protects_torn_writes(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 1, t)
    # simulate a torn write: step dir without marker
    os.makedirs(tmp_path / "step_000000002")
    assert latest_step(str(tmp_path)) == 1


def test_structure_mismatch_rejected(tmp_path):
    save_checkpoint(str(tmp_path), 1, _tree())
    with pytest.raises(AssertionError):
        restore_checkpoint(str(tmp_path), 1, {"different": jnp.zeros(1)})
