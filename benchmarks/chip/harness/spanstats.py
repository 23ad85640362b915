"""Self time of the program's own spans (``repro.obs`` events).

A span's self time is its duration less the part of it that spans nested
inside it on the same thread cover.
"""
from __future__ import annotations

from harness.trace import union


def self_time_s(events, names) -> float | None:
    """Summed self seconds of the complete spans named in ``names``;
    ``None`` when there is no such span."""
    by_tid: dict = {}
    for e in events:
        if e.get("ph") == "X" and isinstance(e.get("tid"), int):
            by_tid.setdefault(e["tid"], []).append(
                (e["t0"], e["t0"] + e["dur"], e["name"]))
    total = 0
    found = False
    for spans in by_tid.values():
        spans.sort(key=lambda s: (s[0], -s[1]))
        for i, (a, b, name) in enumerate(spans):
            if name not in names:
                continue
            found = True
            inner = []
            for c, d, _ in spans[i + 1:]:
                if c >= b:
                    break
                if d <= b and (c, d) != (a, b):
                    inner.append((c, d))
            total += (b - a) - sum(y - x for x, y in union(inner))
    return total / 1e9 if found else None
