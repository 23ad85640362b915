"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` only.  The traced window is a host
annotation (``bench.traced``: the part of its window a traffic driver
traces).  Device busy time is the union of the intervals of the device's
operations inside that window, averaged over the devices that ran any;
idle is the rest of the window.
Kernel time is the summed duration of a compiled program's executions, by
the program's name (``jit_run`` for ``jax.jit(run)``).  Idle gaps are
labelled with the innermost benchmark span (``bench.*`` annotations) that
covers the gap's middle.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = r"^/device:TPU:\d+$"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(log_dir) -> str:
    paths = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals; returns them sorted and disjoint."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo, hi) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def op_base(name: str) -> str:
    """``%while.24 = (s32[], ...) while(...)`` -> ``%while.24``."""
    return name.split(" = ", 1)[0].strip()


def module_base(name: str) -> str:
    """``jit_run(123)`` -> ``jit_run``."""
    return name.split("(", 1)[0].strip()


class TraceEvents:
    """Events of one trace, grouped as the reduction needs them."""

    def __init__(self, path, *, device_plane=DEVICE_PLANE, op_line=OP_LINE,
                 module_line=MODULE_LINE, host_plane=HOST_PLANE,
                 op_name=None):
        from jax.profiler import ProfileData  # noqa: PLC0415
        pd = ProfileData.from_file(str(path))
        dev_re = re.compile(device_plane)
        self.device_ops: dict = {}      # device plane -> [(name, s, e)]
        self.device_modules: dict = {}  # device plane -> [(name, s, e)]
        self.host: list = []            # [(name, s, e)] host annotations
        for plane in pd.planes:
            if dev_re.match(plane.name):
                for line in plane.lines:
                    if line.name == op_line:
                        self.device_ops.setdefault(plane.name, []).extend(
                            _events(line, op_name))
                    elif line.name == module_line:
                        self.device_modules.setdefault(
                            plane.name, []).extend(_events(line, None))
            if plane.name == host_plane:
                for line in plane.lines:
                    self.host.extend(_events(line, None))


def _events(line, name_filter) -> list:
    out = []
    for ev in line.events:
        name = ev.name
        if name_filter is not None and not name_filter(name):
            continue
        s = float(ev.start_ns)
        out.append((name, s, s + float(ev.duration_ns)))
    return out


def window_of(events: TraceEvents, name: str = "bench.window") -> tuple:
    spans = [(s, e) for n, s, e in events.host if n == name]
    if not spans:
        raise ValueError(f"no {name!r} annotation in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce_trace(events: TraceEvents, window: tuple, *,
                 span_prefix: str = "bench.", top: int = 10) -> dict:
    """Busy and idle seconds, per-op and per-program device seconds, and
    the longest idle gaps labelled by benchmark span, inside ``window``
    (nanoseconds on the trace's clock)."""
    lo, hi = window
    per_device_busy = {}
    gaps: list = []
    ops: dict = {}
    for plane, evs in events.device_ops.items():
        busy = union(clip([(s, e) for _, s, e in evs], lo, hi))
        if not busy:
            continue
        per_device_busy[plane] = sum(b - a for a, b in busy) / 1e9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        for n, s, e in evs:
            c = min(e, hi) - max(s, lo)
            if c > 0:
                b = op_base(n)
                ops[b] = ops.get(b, 0.0) + c / 1e9
    modules: dict = {}
    for evs in events.device_modules.values():
        for n, s, e in evs:
            c = min(e, hi) - max(s, lo)
            if c > 0:
                b = module_base(n)
                modules[b] = modules.get(b, 0.0) + c / 1e9
    bench = [(n, s, e) for n, s, e in events.host
             if n.startswith(span_prefix) and e > lo and s < hi]
    labelled: dict = {}
    for a, b in gaps:
        labelled.setdefault(_label(bench, (a + b) / 2), []).append(
            (b - a) / 1e9)
    longest = sorted(((label, d) for label, ds in labelled.items()
                      for d in ds), key=lambda x: -x[1])[:top]
    n_dev = max(1, len(per_device_busy))
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(per_device_busy.values()) / n_dev,
        "per_device_busy_s": per_device_busy,
        "devices": len(per_device_busy),
        "ops_s": ops,
        "modules_s": modules,
        "breakdown": {
            "device_ops": [[n, s] for n, s in sorted(
                ops.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": [[n, s] for n, s in longest],
        },
    }


def _label(bench, t) -> str:
    """The innermost (shortest) benchmark span that covers ``t``."""
    best = None
    for n, s, e in bench:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else "host.other"
