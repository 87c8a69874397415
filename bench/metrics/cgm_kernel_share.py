"""Kernels (``kernels/crm_update.py``, ``clique_density.py``,
``merge_step.py``): share of the device's busy time spent in Mosaic
kernels, in %.  A Mosaic kernel is an op whose text names
``custom_call_target="tpu_custom_call"``; on the live path these are the
three CGM kernels (the trace names them ``crm_update`` and so on)."""
MOSAIC = 'custom_call_target="tpu_custom_call"'


def read(r):
    s = sum(t for name, t in r.trace.op_s.items() if MOSAIC in name)
    if s <= 0.0 or r.trace.busy_s <= 0.0:
        return None
    return 100.0 * s / r.trace.busy_s
