"""What the host was doing while the device sat idle, and the CPU time of
the program's spans.

The program's ``repro.obs`` spans are profiler annotations while its
tracer is enabled, so a profiler trace holds them on the host plane, one
line per thread, on the device trace's clock.

* :func:`idle_attribution` credits each instant of device idle (the
  complement of the union of the device's operations, as
  :func:`harness.trace.reduce_trace` computes it) to the worker threads
  with a worker annotation open at that instant: each of the n such lines
  gets 1/n of the instant, credited to its innermost open program
  annotation.  With no worker open the instant goes to the innermost
  benchmark span (``bench.*``), as the breakdown labels its gaps.  The
  credited seconds sum to the idle seconds.
* :func:`cpu_self_time_s` is :func:`harness.spanstats.self_time_s` read
  from the spans' thread CPU time (``cpu``) instead of their wall time.
"""
from __future__ import annotations

from bisect import bisect_right

from harness.trace import HOST_PLANE, _label, clip, union

WORKER = "campaign.worker"
# the host waiting on the device path: dispatch and its lock, the kernel
# lock, and the wait for a shard's results
KERNEL_WAIT_SPANS = ("wave.dispatch", "wave.dispatch_lock_wait",
                     "wave.lock_wait", "wave.result_wait")


def host_lines(path, host_plane=HOST_PLANE) -> list:
    """The host plane's annotations line by line (one list of
    ``(name, start_ns, end_ns)`` per thread line)."""
    from jax.profiler import ProfileData  # noqa: PLC0415
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if plane.name != host_plane:
            continue
        for line in plane.lines:
            evs = [(ev.name, float(ev.start_ns),
                    float(ev.start_ns) + float(ev.duration_ns))
                   for ev in line.events]
            if evs:
                out.append(evs)
    return out


def idle_gaps(events, window) -> list:
    """Per device that ran an operation in ``window``: its idle intervals
    there."""
    lo, hi = window
    out = []
    for evs in events.device_ops.values():
        busy = union(clip([(s, e) for _, s, e in evs], lo, hi))
        if not busy:
            continue
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        out.append([(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]])
    return out


def _line_states(line, worker, span_prefix):
    """A thread line as a step function: breakpoint times and, from each
    one on, ``(worker open, innermost open program annotation)``."""
    # a tie on both ends goes to the later event of the line: the child
    evs = sorted((s, e, k, n) for k, (n, s, e) in enumerate(line)
                 if not n.startswith(span_prefix))
    times = sorted({x for s, e, _, _ in evs for x in (s, e)})
    states = []
    active: list = []
    k = 0
    for t in times:
        active = [a for a in active if a[1] > t]
        while k < len(evs) and evs[k][0] <= t:
            if evs[k][1] > t:
                active.append(evs[k])
            k += 1
        if active:
            inner = max(active, key=lambda a: (a[0], -a[1], a[2]))
            states.append((any(a[3] == worker for a in active), inner[3]))
        else:
            states.append((False, None))
    return times, states


def idle_attribution(events, lines, window, *, worker: str = WORKER,
                     span_prefix: str = "bench.") -> dict:
    """Idle seconds of ``window`` (mean over the devices that ran an
    operation there, as ``reduce_trace``'s ``busy_s``) and their credit by
    annotation name; ``None`` when no device ran an operation.

    ``events`` is a :class:`harness.trace.TraceEvents` of the same trace
    as ``lines`` (:func:`host_lines`)."""
    per_device = idle_gaps(events, window)
    if not per_device:
        return None
    lo, hi = window
    bench = [(n, s, e) for n, s, e in events.host
             if n.startswith(span_prefix) and e > lo and s < hi]
    workers = [_line_states(line, worker, span_prefix) for line in lines
               if any(n == worker for n, _, _ in line)]
    edges = sorted({x for _, s, e in bench for x in (s, e)})
    weight = 1.0 / len(per_device)
    credit: dict = {}
    idle_ns = 0.0
    for gaps in per_device:
        for a, b in gaps:
            idle_ns += b - a
            cuts = {a, b}
            cuts.update(edges[bisect_right(edges, a):bisect_right(edges, b)])
            for times, _ in workers:
                i = bisect_right(times, a)
                j = bisect_right(times, b)
                cuts.update(times[i:j])
            cuts = sorted(x for x in cuts if a <= x <= b)
            for x, y in zip(cuts, cuts[1:]):
                labels = []
                for times, states in workers:
                    i = bisect_right(times, x) - 1
                    if i >= 0 and states[i][0]:
                        labels.append(states[i][1])
                if labels:
                    share = (y - x) * weight / len(labels)
                    for label in labels:
                        credit[label] = credit.get(label, 0.0) + share
                else:
                    label = _label(bench, (x + y) / 2)
                    credit[label] = credit.get(label, 0.0) + (y - x) * weight
    return {"idle_s": idle_ns * weight / 1e9,
            "by_span": {n: ns / 1e9 for n, ns in sorted(
                credit.items(), key=lambda kv: -kv[1])}}


def idle_share(attribution, names) -> float | None:
    """Percent of the idle seconds credited to the spans in ``names``."""
    if not attribution or attribution["idle_s"] <= 0:
        return None
    got = sum(attribution["by_span"].get(n, 0.0) for n in names)
    return 100.0 * got / attribution["idle_s"]


def cpu_self_time_s(events, names) -> float | None:
    """Summed thread-CPU self seconds of the complete spans named in
    ``names``: each span's ``cpu`` less that of the spans directly nested
    in it on the same thread.  ``None`` when there is no such span or a
    span it reads has no ``cpu``."""
    by_tid: dict = {}
    for e in events:
        if e.get("ph") == "X" and isinstance(e.get("tid"), int):
            by_tid.setdefault(e["tid"], []).append(
                (e["t0"], e["t0"] + e["dur"], e["name"], e.get("cpu")))
    total = 0
    found = False
    for spans in by_tid.values():
        spans.sort(key=lambda s: (s[0], -s[1]))
        for i, (a, b, name, cpu) in enumerate(spans):
            if name not in names:
                continue
            if cpu is None:
                return None
            found = True
            end = a    # children: nested spans not inside an earlier one
            for c, d, _, ccpu in spans[i + 1:]:
                if c >= b:
                    break
                if d <= b and c >= end:
                    if ccpu is None:
                        return None
                    cpu -= ccpu
                    end = d
            total += cpu
    return total / 1e9 if found else None


def span_arg_sum(events, name: str, key: str) -> int | None:
    """Sum of attribute ``key`` over the complete spans called ``name``;
    ``None`` when no such span carries it."""
    vals = [e["args"][key] for e in events
            if e.get("ph") == "X" and e.get("name") == name
            and e.get("args") and key in e["args"]]
    return sum(vals) if vals else None
