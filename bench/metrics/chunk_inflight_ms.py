"""Live engine (``serving/live.py``) dispatch: median time from a chunk's
dispatch to its probe being ready on the device, harness clock."""
import numpy as np


def read(r):
    t = r.harness.get("chunk_inflight_ms")
    if t is None or not len(t):
        return None
    return float(np.median(t))
