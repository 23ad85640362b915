"""Run one cell traced, as ``run_cell.py --trace 1`` does, and print which
of the program's spans the device's idle time falls in.

    python3 benchmarks/chip/idle_table.py --workload campaign.sim3 \
        --seed <n> --seconds 30

``run_cell.py`` deletes the profiler trace once it has reduced it, before
the per-layer readers run, and keeps no host line of it; this script reads
the trace just before that (``harness.attribution``) and, after the
result line ``run_cell.py`` prints, prints one more line: ``idle`` and a
JSON object with the idle seconds, their credit by span, the idle seconds
of ``reduce_trace`` for comparison, whether ``reduce_trace`` gives the
same result with the program's annotations taken out of the trace, and
the shares credited to the wave host path
(``wave.lower``/``wave.pack``/``wave.extract``) and to the host waiting on
the device path (dispatch, its locks and result waits).
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    sys.path[:0] = [str(BENCH), str(BENCH.parent.parent / "src")]
    import run_cell  # noqa: PLC0415
    from harness import attribution  # noqa: PLC0415
    from harness.readers import WAVE_HOST_SPANS  # noqa: PLC0415
    from harness.trace import (TraceEvents, find_xplane,  # noqa: PLC0415
                               reduce_trace, window_of)

    got: dict = {}
    reduce_measures = run_cell.trace_measures

    def trace_measures(cell, *args):
        path = find_xplane(cell.trace_dir)
        events = TraceEvents(path)
        window = window_of(events, "bench.traced")
        got["attribution"] = attribution.idle_attribution(
            events, attribution.host_lines(path), window)
        events.host = [h for h in events.host if h[0].startswith("bench.")]
        bare = reduce_trace(events, window)
        m = reduce_measures(cell, *args)
        got["reduce_idle_s"] = m["trace"]["window_s"] - m["trace"]["busy_s"]
        got["reduction_unchanged"] = all(
            m["trace"][k] == v for k, v in bare.items())
        return m

    run_cell.trace_measures = trace_measures
    rc = run_cell.main(argv + ["--trace", "1"])
    att = got.get("attribution")
    if rc or not att:
        return rc or 1
    print("idle " + json.dumps({
        **att, "reduce_idle_s": got["reduce_idle_s"],
        "reduction_unchanged": got["reduction_unchanged"],
        "idle_wave_host_pct": attribution.idle_share(att, WAVE_HOST_SPANS),
        "idle_kernel_wait_pct": attribution.idle_share(
            att, attribution.KERNEL_WAIT_SPANS)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
