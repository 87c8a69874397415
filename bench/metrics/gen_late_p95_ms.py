"""Client layer (``bench/traffic``, ``drivers/live.py``): how late the
open-loop generator submitted its slices, submit time minus due time on
the harness clock, 95th percentile over every slice of the window."""
import numpy as np


def read(r):
    late = r.harness.get("gen_late_ms")
    if late is None or not len(late):
        return None
    return float(np.percentile(late, 95))
