"""Kernels: ``clique_pair_edges``'s share of the device's roofline, in %,
from its Mosaic calls' device time in the trace and its operations and bytes in
``kernel_costs`` (whose docstring says what is counted, and why the
share is a lower bound on the f32 one)."""
import kernel_costs


def read(r):
    return kernel_costs.share(r.trace.op_s, r.harness, "clique_pair_edges",
                              kernel_costs.device_peaks())
