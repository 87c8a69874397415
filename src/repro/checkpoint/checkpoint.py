"""Checkpointing: save/restore arbitrary pytrees, elastic re-shard on
restore.

Layout per step:  <dir>/step_<N>/
    manifest.msgpack   tree structure, leaf paths, shapes, dtypes, meta
    arrays.npz         one entry per leaf (path-keyed)
    _COMMITTED         write-completion marker (atomic rename publish)

Restore accepts a ``shardings`` pytree: leaves are ``jax.device_put`` onto
it — so a checkpoint written on one mesh restores onto ANY mesh/device
count (elastic scaling).  The commit marker guarantees a crashed writer
never publishes a torn checkpoint, and ``latest_step`` picks the newest
committed step.
"""
from __future__ import annotations

import os
import shutil

import ml_dtypes  # numpy dtype extensions (bf16 etc.) — ships with jax
import msgpack
import numpy as np

import jax

_UINT_OF_SIZE = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}


def _encode(a: np.ndarray) -> tuple[np.ndarray, str]:
    """npz cannot store ml_dtypes (bf16 saves as void); view as uint."""
    name = a.dtype.name
    if a.dtype.kind in "fiub" and not name.startswith(("bfloat", "float8")):
        return a, name
    return a.view(_UINT_OF_SIZE[a.dtype.itemsize]), name


def _decode(raw: np.ndarray, name: str) -> np.ndarray:
    if raw.dtype.name == name:
        return raw
    return raw.view(getattr(ml_dtypes, name, np.dtype(name)))


def _flatten(tree):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
             for p, _ in leaves]
    return paths, [l for _, l in leaves], treedef


def save_checkpoint(directory: str, step: int, tree, meta: dict | None = None) -> str:
    paths, leaves, _ = _flatten(tree)
    step_dir = os.path.join(directory, f"step_{step:09d}")
    tmp = step_dir + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    arrays = {p: np.asarray(l) for p, l in zip(paths, leaves)}
    encoded, names = {}, {}
    for p, a in arrays.items():
        encoded[p], names[p] = _encode(a)
    np.savez(os.path.join(tmp, "arrays.npz"), **encoded)
    manifest = {
        "step": step,
        "paths": paths,
        "shapes": [list(a.shape) for a in arrays.values()],
        "dtypes": [names[p] for p in paths],
        "meta": meta or {},
    }
    with open(os.path.join(tmp, "manifest.msgpack"), "wb") as f:
        f.write(msgpack.packb(manifest))
    with open(os.path.join(tmp, "_COMMITTED"), "w") as f:
        f.write("ok")
    if os.path.exists(step_dir):
        shutil.rmtree(step_dir)
    os.rename(tmp, step_dir)               # atomic publish
    return step_dir


def latest_step(directory: str) -> int | None:
    if not os.path.isdir(directory):
        return None
    steps = []
    for name in os.listdir(directory):
        if name.startswith("step_") and not name.endswith(".tmp"):
            if os.path.exists(os.path.join(directory, name, "_COMMITTED")):
                steps.append(int(name.split("_")[1]))
    return max(steps) if steps else None


def load_checkpoint_tree(directory: str, step: int) -> tuple[dict, dict]:
    """Read a checkpoint back as a nested dict of numpy arrays (no ``like``
    tree needed — only for checkpoints whose tree is dicts all the way
    down, e.g. CacheSession snapshots).  Returns (tree, meta)."""
    step_dir = os.path.join(directory, f"step_{step:09d}")
    with open(os.path.join(step_dir, "manifest.msgpack"), "rb") as f:
        manifest = msgpack.unpackb(f.read())
    z = np.load(os.path.join(step_dir, "arrays.npz"))
    dtype_of = dict(zip(manifest["paths"], manifest["dtypes"]))
    out: dict = {}
    for path in manifest["paths"]:
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = _decode(z[path], dtype_of[path])
    return out, manifest["meta"]


def restore_checkpoint(directory: str, step: int, like, shardings=None):
    """Restore into the structure of ``like`` (a pytree of arrays or
    ShapeDtypeStructs).  ``shardings``: optional matching pytree of
    ``jax.sharding.Sharding`` for elastic placement."""
    step_dir = os.path.join(directory, f"step_{step:09d}")
    with open(os.path.join(step_dir, "manifest.msgpack"), "rb") as f:
        manifest = msgpack.unpackb(f.read())
    z = np.load(os.path.join(step_dir, "arrays.npz"))
    paths, like_leaves, treedef = _flatten(like)
    assert set(paths) == set(manifest["paths"]), "checkpoint/tree mismatch"
    out = []
    shard_leaves = (
        jax.tree_util.tree_leaves(shardings) if shardings is not None else
        [None] * len(like_leaves)
    )
    dtype_of = dict(zip(manifest["paths"], manifest["dtypes"]))
    for p, l, s in zip(paths, like_leaves, shard_leaves):
        a = _decode(z[p], dtype_of[p])
        a = a.astype(l.dtype) if hasattr(l, "dtype") else a
        out.append(jax.device_put(a, s) if s is not None else a)
    return jax.tree_util.tree_unflatten(treedef, out), manifest["meta"]
