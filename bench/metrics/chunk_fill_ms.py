"""Live engine (``serving/live.py``) chunking: median time from a chunk's
first due request to the ``submit`` that dispatched it, harness clock,
over the chunks that began inside the window."""
import numpy as np


def read(r):
    fill = r.harness.get("chunk_fill_ms")
    if fill is None or not len(fill):
        return None
    return float(np.median(fill))
