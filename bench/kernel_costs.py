"""Operations and bytes of the three Mosaic CGM kernels, from the shapes
each is given, and their share of the device's roofline.

One kernel call runs at one T_CG boundary of one lane (a vmapped call
covers every lane of its group at once), so each function takes the
number of lane-boundaries ``lanes`` beside the boundary's sizes: the
padded hot capacity ``h``, the merge space ``scap`` and the window
buffer ``wcap`` rows (``SweepEngine.stats``).  The counts are of the
algorithm on those shapes, without the kernels' own padding to 128-wide
tiles, and the bytes are each operand read once and each result written
once, which no schedule can go below.

* ``crm_update``: the (h, h) co-occurrence counts ``H^T H`` of the
  (wcap, h) one-hot window incidence;
* ``clique_pair_edges``: ``X = (M A) M^T`` with M (scap, h) and A (h, h),
  two matmuls (two custom calls in the trace);
* ``merge_density``: the (scap, scap) merge matrix from X, two additions
  per entry, reading X, the within-counts and sizes, writing D.

The kernels compute in float32 (``clique_pair_edges`` at
``Precision.HIGHEST``), while the table's peak is the bf16 one: an f32
matmul takes several bf16 passes, so a share against the bf16 peak is a
lower bound on the share of what the MXU can do in f32.
"""
from __future__ import annotations

import json
import os

import tracing

MOSAIC = 'custom_call_target="tpu_custom_call"'
_F32 = 4


def crm_update(h: int, wcap: int, lanes: int, **_) -> tuple[int, int]:
    return (2 * wcap * h * h * lanes,
            _F32 * (wcap * h + h * h) * lanes)


def clique_pair_edges(h: int, scap: int, lanes: int,
                      **_) -> tuple[int, int]:
    return (2 * scap * h * (h + scap) * lanes,
            _F32 * (scap * h + h * h + scap * scap) * lanes)


def merge_density(scap: int, lanes: int, **_) -> tuple[int, int]:
    return (2 * scap * scap * lanes,
            _F32 * (2 * scap * scap + 4 * scap) * lanes)


COSTS = {"crm_update": crm_update, "clique_pair_edges": clique_pair_edges,
         "merge_density": merge_density}


def kernel_seconds(op_s: dict, kernel: str) -> float:
    """Device seconds of ``kernel``'s Mosaic calls: ops named
    ``%<kernel>.<k> = ... custom-call(...)`` with the Mosaic target."""
    return sum(s for name, s in op_s.items() if MOSAIC in name
               and tracing.short(name).split(".")[0] == kernel)


def share(op_s: dict, harness: dict, kernel: str, peaks: dict):
    """``kernel``'s achieved rate over min(peak FLOP/s, intensity x HBM
    bandwidth), in %: the least time its operations and bytes take at
    the peaks, over its device time.  None where the trace holds no such
    kernel or the program reported no sizes."""
    lanes = harness.get("cgm_boundaries", 0)
    t = kernel_seconds(op_s, kernel)
    if not lanes or t <= 0.0:
        return None
    flops, nbytes = COSTS[kernel](
        h=harness["cgm_hot"], scap=harness["cgm_merge_space"],
        wcap=harness["cgm_window_rows"], lanes=lanes)
    least = max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / t


def device_peaks() -> dict:
    """The peaks of the device this process runs on (``peaks.json``)."""
    import jax

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        return json.load(f)["devices"][jax.devices()[0].device_kind]
