"""Reduction of a profiler trace to device busy time, program and kernel
time, and the device's idle gaps labelled by the harness's host spans.

The profiler writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` into plain lists of ``(name, start_ns,
duration_ns)`` so that :func:`reduce` can be tested on a hand-built trace.
A device plane is one named ``/device:TPU:<i>``; its ``XLA Ops``
line holds the operations that ran, its ``XLA Modules`` line the programs.
Host spans are the harness's ``TraceAnnotation`` names, all of which
start with ``SPAN_PREFIX``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class Plane:
    """One device: its operations and its programs, as (name, start, dur)."""

    name: str
    ops: list
    modules: list
    #: profiler stats of a few custom-call ops (kernels), for inspection
    kernel_stats: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Reduced:
    """What the per-layer metric readers and the breakdown read."""

    window_s: float                 # length of the traced window
    busy_s: float                   # union of op intervals, mean over devices
    op_s: dict                      # op name -> summed device seconds
    module_s: dict                  # program name -> summed device seconds
    idle_gaps: list                 # [(host span name, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def start(trace_dir: str) -> None:
    """Start the profiler without its Python-call tracer: an open loop
    spins through millions of calls, and a 51 s trace holding them took
    more than the 40 GiB of host memory of a one-chip v5e machine.  The
    harness's spans are kept."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def load(trace_dir: str):
    """(device planes, host spans) of the newest trace under trace_dir."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices, spans = [], []
    for pl in pd.planes:
        if _DEVICE_PLANE.match(pl.name):
            lines = {ln.name: ln for ln in pl.lines}
            devices.append(Plane(
                pl.name,
                _events(lines.get(OPS_LINE)),
                _events(lines.get(MODULES_LINE)),
                _kernel_stats(lines.get(OPS_LINE))))
        elif pl.name.startswith("/host:"):
            for ln in pl.lines:
                spans.extend((ev.name, int(ev.start_ns), int(ev.duration_ns))
                             for ev in ln.events
                             if ev.name.startswith(SPAN_PREFIX))
    return devices, spans


def _events(line) -> list:
    if line is None:
        return []
    return [(ev.name, int(ev.start_ns), int(ev.duration_ns))
            for ev in line.events]


def _kernel_stats(line, k: int = 3) -> list:
    out, seen = [], set()
    for ev in (line.events if line is not None else ()):
        head = ev.name.split(" = ", 1)[0]
        if "custom-call" in head and head not in seen:
            seen.add(head)
            out.append((ev.name[:300], [(str(a), str(b)[:300])
                                        for a, b in ev.stats]))
            if len(out) == k:
                break
    return out


def union_ns(intervals) -> list:
    """Merged [start, end) intervals of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(devices: list, spans: list, window_ns: tuple,
           n_gaps: int = 10) -> Reduced:
    """Busy union and idle share per device over ``window_ns`` (start, end
    on the trace's clock), op and program totals, and the longest idle
    gaps of the first device, each named by a host span (``_label``)."""
    w0, w1 = window_ns
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy, op_s, module_s = [], {}, {}
    first_union = None
    for pl in devices:
        iv = [(max(s, w0), min(s + d, w1)) for _, s, d in pl.ops
              if s < w1 and s + d > w0]
        u = union_ns(iv)
        if first_union is None:
            first_union = u
        busy.append(sum(e - s for s, e in u))
        for name, _, d in pl.ops:
            op_s[name] = op_s.get(name, 0.0) + d * 1e-9
        for name, _, d in pl.modules:
            module_s[name] = module_s.get(name, 0.0) + d * 1e-9
    gaps, prev = [], w0
    for s, e in first_union + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [(_label(g, spans), (g[1] - g[0]) * 1e-9)
                for g in gaps[:n_gaps]]
    return Reduced(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy) / len(busy) * 1e-9,
        op_s=op_s, module_s=module_s, idle_gaps=labelled)


def _label(gap, spans) -> str:
    """The innermost host span that covers at least half of ``gap``, else
    the one that covers most of it ("untraced host" where none does)."""
    half = (gap[1] - gap[0]) / 2
    best, best_key = "untraced host", None
    for name, s, d in spans:
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov <= 0:
            continue
        key = (ov >= half, -d if ov >= half else ov)
        if best_key is None or key > best_key:
            best, best_key = name[len(SPAN_PREFIX):], key
    return best


#: control-flow ops whose device time includes the ops nested in them
CONTAINERS = ("while", "conditional", "call")


def short(name: str) -> str:
    """``%fusion.711 = (f32[...]) fusion(...)`` -> ``fusion.711``."""
    return name.split(" = ", 1)[0].lstrip("%")


def top(d: dict, k: int = 10, leaves: bool = False) -> list:
    """The k largest entries of a name -> seconds map as [short name,
    seconds]; ``leaves`` leaves out control-flow containers."""
    items = [(short(n), s) for n, s in d.items()]
    if leaves:
        items = [(n, s) for n, s in items
                 if n.split(".")[0] not in CONTAINERS]
    return [[n, s] for n, s in sorted(items, key=lambda x: -x[1])[:k]]
