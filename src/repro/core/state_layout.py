"""Device state layouts: dense / bucketed / row-sharded (DESIGN.md §13).

The JAX replay engine keeps the cache state as an ``(n + 1, m)`` expiry
matrix + ``(n + 1,)`` anchor vector (one row per possible clique id plus
the dump row absorbing masked scatters).  That DENSE geometry bakes the
exact catalog/server shape into every compiled scan, which breaks down
in two places the paper's scalability story (fig8) and the ROADMAP's
catalog targets care about:

* **heterogeneous grids** — fig8 varies (n, m) per point, so no two
  points share a compiled shape and a mixed sweep pays one XLA compile
  per point instead of one per cohort;
* **big catalogs** — at n ~ 10^4-10^5 the state matrix stops being a
  single-chip afterthought and wants to be split across devices.

:class:`StateLayout` makes the geometry an explicit, threadable policy:

``dense``
    Today's ``(n + 1, m)`` layout, bitwise default.  Every existing
    entry point resolves ``layout=None`` to this.

``bucketed``
    Rows (catalog) and columns (servers) round UP to padding buckets:
    state is ``(bucket(n) + 1, bucket(m))`` with the dump row moved to
    the LAST row.  Points whose (n, m) fall in the same bucket share
    one compiled scan — a mixed-shape sweep compiles per bucket COHORT,
    not per point.  Padded rows/columns are inert by the same masking
    rules as padded events: rows above the live prefix are never
    gathered by real events, padded columns hold zeros forever (event
    scatters only touch j < m, install seeding only targets real
    servers).

``row_sharded``
    The dense geometry with rows padded to a multiple of the shard
    count and the state rows distributed over a mesh axis via
    ``NamedSharding`` — for catalogs one chip can't hold.  The scan is
    unchanged; GSPMD partitions the row-indexed gathers/scatters.

The layout owns exactly three decisions — state dims, dump-row index,
device placement — so threading it through a layer means passing it to
``fresh_state_arrays`` / ``state_to_device`` / ``build_schedule`` and
nothing else.  Schedules record the geometry they were built for
(``ReplaySchedule.nrow`` / ``ncol``); host-side :class:`CacheState`
stays dense ``(k, m)`` under every layout, which is what makes
snapshots freely portable between dense and bucketed sessions.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

LAYOUT_KINDS = ("dense", "bucketed", "row_sharded")


def _round_up(x: int, step: int) -> int:
    return -(-int(x) // int(step)) * int(step)


@dataclasses.dataclass(frozen=True)
class StateLayout:
    """Geometry + placement policy for the device cache state.

    Parameters
    ----------
    kind : "dense" | "bucketed" | "row_sharded".
    row_bucket, col_bucket : bucket steps for ``bucketed`` (catalog rows
        round up to ``row_bucket`` multiples, server columns to
        ``col_bucket``).  Ignored by the other kinds.
    mesh : a ``jax.sharding.Mesh`` carrying ``row_axis`` — required for
        ``row_sharded`` placement.  Without a mesh the row-sharded
        GEOMETRY (rows padded to a shard multiple) still applies, so the
        layout can be unit-tested on one device.
    shards : explicit row-shard count; defaults to the mesh's
        ``row_axis`` size (1 without a mesh).
    row_axis : mesh axis name the state rows are distributed over.
    """

    kind: str = "dense"
    row_bucket: int = 1024
    col_bucket: int = 256
    mesh: Any = None
    shards: int | None = None
    row_axis: str = "state_row"

    def __post_init__(self):
        if self.kind not in LAYOUT_KINDS:
            raise ValueError(
                f"unknown state layout {self.kind!r}; choose from "
                f"{LAYOUT_KINDS}")
        if self.kind == "row_sharded" and self.row_shards < 1:
            raise ValueError("row_sharded layout needs shards >= 1")

    # -- construction helpers ---------------------------------------------
    @classmethod
    def resolve(cls, layout) -> "StateLayout":
        """None -> dense; str -> default layout of that kind; pass-through."""
        if layout is None:
            return DENSE
        if isinstance(layout, str):
            if layout == "row_sharded":
                raise ValueError(
                    "row_sharded needs a mesh (or explicit shards); "
                    "construct StateLayout(kind='row_sharded', mesh=...)")
            return cls(kind=layout)
        if not isinstance(layout, StateLayout):
            raise TypeError(f"not a StateLayout: {layout!r}")
        return layout

    # -- geometry ----------------------------------------------------------
    @property
    def row_shards(self) -> int:
        """Number of row shards (1 for dense/bucketed)."""
        if self.kind != "row_sharded":
            return 1
        if self.shards is not None:
            return int(self.shards)
        if self.mesh is not None and self.row_axis in self.mesh.axis_names:
            return int(self.mesh.shape[self.row_axis])
        return 1

    def state_rows(self, n: int) -> int:
        """Device state rows INCLUDING the dump row (always the last)."""
        if self.kind == "dense":
            return n + 1
        if self.kind == "bucketed":
            return _round_up(max(n, 1), self.row_bucket) + 1
        return _round_up(n + 1, self.row_shards)

    def state_cols(self, m: int) -> int:
        if self.kind == "bucketed":
            return _round_up(max(m, 1), self.col_bucket)
        return m

    def state_dims(self, n: int, m: int) -> tuple[int, int]:
        """(rows, cols) of the device expiry matrix for an (n, m) catalog."""
        return self.state_rows(n), self.state_cols(m)

    def dump_row(self, n: int) -> int:
        """Index of the masked-scatter dump row (always rows - 1)."""
        return self.state_rows(n) - 1

    def is_dense_for(self, n: int, m: int) -> bool:
        """True iff this layout reproduces the dense geometry bitwise at
        (n, m) — the eligibility condition for paths whose scan derives
        its dump row from ``n`` rather than the carry."""
        return self.row_shards == 1 and self.state_dims(n, m) == (n + 1, m)

    def supports_device_cgm(self, n: int, m: int) -> bool:
        """True iff the device-resident CGM may back an (n, m) catalog.

        The CGM carry is built DENSE-n regardless of this layout (its
        hot-space embeds and install reductions size themselves from the
        carry, not from the schedule geometry), so any single-shard
        layout qualifies — including ``bucketed``, whose padded generic
        schedules never reach the CGM path.  Row-sharded state does not:
        the in-scan segment reductions need the whole slot map on one
        device."""
        del n, m
        return self.row_shards == 1

    def state_bytes(self, n: int, m: int) -> int:
        """Device bytes of one scenario's state (f64 E + i32 anchor)."""
        rows, cols = self.state_dims(n, m)
        return rows * cols * 8 + rows * 4

    def state_bytes_per_device(self, n: int, m: int) -> int:
        """Per-device state bytes (row-sharded splits rows evenly)."""
        return self.state_bytes(n, m) // self.row_shards

    # -- placement ---------------------------------------------------------
    def place_state(self, E0, anchor0):
        """Commit (E0, anchor0) to the row-sharded mesh placement.

        ``E0``/``anchor0`` may carry a leading scenario axis; the row
        axis is always the second-to-last of E0.  A no-op (returns the
        inputs) unless this layout actually spans > 1 device.
        """
        sh = self.state_shardings(np.ndim(E0) - 2)
        if sh is None:
            return E0, anchor0
        import jax

        return jax.device_put(E0, sh[0]), jax.device_put(anchor0, sh[1])

    def state_shardings(self, lead: int):
        """The (E, anchor) placements of :meth:`place_state` for state
        with ``lead`` leading lane axes, or None where it is a no-op."""
        if self.kind != "row_sharded" or self.mesh is None \
                or self.row_axis not in self.mesh.axis_names \
                or int(self.mesh.shape[self.row_axis]) <= 1:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        pfx = (None,) * lead
        return (NamedSharding(self.mesh, P(*pfx, self.row_axis, None)),
                NamedSharding(self.mesh, P(*pfx, self.row_axis)))

    # -- snapshot wire format ---------------------------------------------
    @property
    def tag(self) -> str:
        return self.kind

    def check_restore(self, snap_tag: str, snap_shards: int) -> None:
        """Restore-compatibility rule (ISSUE 8): dense <-> bucketed are
        freely interchangeable (host state is dense either way); a
        row-sharded snapshot restored into a row-sharded session must
        match the mesh's shard count."""
        if snap_tag == "row_sharded" and self.kind == "row_sharded" \
                and int(snap_shards) != self.row_shards:
            raise ValueError(
                f"snapshot state layout is row_sharded over {snap_shards} "
                f"shard(s), session mesh has {self.row_shards}; restore "
                "on a matching mesh (or a dense/bucketed session)")


#: the bitwise-default layout every ``layout=None`` resolves to
DENSE = StateLayout()


def _auto(n_axes: int) -> dict:
    import jax

    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


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
    import jax

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
