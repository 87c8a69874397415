"""Device scan step (``engine_jax._replay_impl``, ``cgm_jax._cgm_replay_impl``
and the live engine's jitted ``step`` around either): device seconds of
those programs in the trace, in ms per 10^6 requests priced (a sweep's
scenario-requests) in the traced window."""
import re

SCAN = re.compile(r"jit_step|_replay_impl|_cgm_replay_impl")


def read(r):
    ms = sum(s for name, s in r.trace.module_s.items() if SCAN.search(name))
    n = r.harness.get("requests", 0)
    if ms <= 0.0 or n <= 0:
        return None
    return ms * 1e3 / (n / 1e6)
