"""Production mesh builders.  FUNCTIONS ONLY — importing this module never
touches jax device state (required by the dry-run contract)."""
from __future__ import annotations

import jax


def _auto(n_axes: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto(len(axes)))


def make_test_mesh(n_devices: int | None = None):
    """Small mesh for CPU tests: (data=2, model=n/2)."""
    n = n_devices or len(jax.devices())
    kw = _auto(2)
    if n == 1:
        return jax.make_mesh((1, 1), ("data", "model"), **kw)
    return jax.make_mesh((2, n // 2), ("data", "model"), **kw)


def make_sweep_mesh(n_devices: int | None = None, state_rows: int = 1):
    """Mesh for SweepEngine grid sharding (repro.core.sweep).

    Default: 1-D ("scenario",) — each device replays a slice of the
    stacked scenario axis.  ``state_rows > 1`` splits the devices into a
    2-D ("scenario", "state_row") grid whose second axis carries the
    row-sharded StateLayout: the (n+1, m) expiry/anchor rows of every
    lane are distributed over ``state_rows`` devices — catalogs one chip
    can't hold.  ``state_rows`` must divide the device count.
    ``n_devices`` takes the first that many of ``jax.devices()`` (a
    sub-mesh of the host); on a single-device host this is a trivial
    mesh and sweeps stay local."""
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"n_devices={n} exceeds the {len(devs)} devices")
    devs = devs[:n]
    if state_rows <= 1:
        return jax.make_mesh((n,), ("scenario",), devices=devs, **_auto(1))
    if n % state_rows:
        raise ValueError(
            f"state_rows={state_rows} must divide the device count {n}")
    return jax.make_mesh((n // state_rows, state_rows),
                         ("scenario", "state_row"), devices=devs,
                         **_auto(2))


def dp_axes(mesh) -> tuple[str, ...]:
    """Axes that carry data parallelism (pod folds into DP)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def dp_size(mesh) -> int:
    s = 1
    for a in dp_axes(mesh):
        s *= mesh.shape[a]
    return s
