"""Program spans on the profiler's timeline.

Every span is a ``jax.profiler.TraceAnnotation`` named ``akpc.<name>``.
With no trace active it costs about a microsecond, so spans sit at chunk,
call, phase and clique-generation-window granularity only: never per
request and never inside jitted code.  While ``jax.profiler`` records, a
span lands on the host plane of the trace on the same nanosecond clock
as the device's ops, with its keyword arguments as event stats.

Device-side work inside the scans is named with ``jax.named_scope``
instead (``cgm_boundary``, ``event_step``, ...): scopes change op
metadata only, never program names.

:func:`read` reads the spans back from a recorded trace.
"""
from __future__ import annotations

import glob
import os

import jax

PREFIX = "akpc."


def span(name: str, **stats):
    """A context manager that records ``akpc.<name>`` with ``stats``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **stats)


def read(trace_dir: str) -> list:
    """``[(name, start_ns, duration_ns, stats)]`` of the ``akpc.`` spans in
    the newest trace that ``jax.profiler`` wrote under ``trace_dir``,
    in start order; ``name`` keeps its prefix and ``stats`` is a dict."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for pl in ProfileData.from_file(paths[-1]).planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            out.extend((ev.name, int(ev.start_ns), int(ev.duration_ns),
                        dict(ev.stats))
                       for ev in ln.events if ev.name.startswith(PREFIX))
    return sorted(out, key=lambda s: s[1])
