"""The trace reduction on a profiler trace recorded here, on the CPU, with
known busy and idle intervals: host annotations named ``op.*`` stand in
for device operations."""
import time

import pytest

from harness.trace import (TraceEvents, clip, find_xplane, reduce_trace,
                           union, window_of)

BUSY = 0.12     # seconds of each stand-in device operation
GAP = 0.06      # seconds of each idle stretch


def test_union_and_clip():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The trace, and each annotation's length by the host's clock (a
    sleep may overrun on a loaded host, so the lengths are measured)."""
    import jax  # noqa: PLC0415
    d = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    spans = []

    def span(name, seconds):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            time.sleep(seconds)
            spans.append((name, time.perf_counter() - t0))

    jax.profiler.start_trace(str(d), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            t0 = time.perf_counter()
            span("bench.lower", GAP)
            span("op.fusion", BUSY)
            span("bench.extract", GAP)
            span("op.while", BUSY)
            span("op.fusion", BUSY)
            spans.append(("bench.traced", time.perf_counter() - t0))
    finally:
        jax.profiler.stop_trace()
    events = TraceEvents(find_xplane(d), device_plane=r"^/host:CPU$",
                         op_line="python",
                         op_name=lambda n: n.startswith("op."))
    return events, spans


def _total(spans, prefix):
    return sum(t for n, t in spans if n.startswith(prefix))


def test_busy_idle_and_breakdown(recorded):
    events, spans = recorded
    red = reduce_trace(events, window_of(events, "bench.traced"))
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(_total(spans, "op."), abs=2e-3)
    assert red["window_s"] == pytest.approx(_total(spans, "bench.traced"),
                                            abs=2e-3)
    ops = dict(red["breakdown"]["device_ops"])
    assert set(ops) == {"op.fusion", "op.while"}
    assert ops["op.fusion"] == pytest.approx(_total(spans, "op.fusion"),
                                             abs=2e-3)
    gaps = red["breakdown"]["idle_gaps"]
    labels = [g[0] for g in gaps[:2]]
    assert sorted(labels) == ["bench.extract", "bench.lower"]
    for label, seconds in gaps[:2]:
        assert seconds == pytest.approx(_total(spans, label), abs=2e-3)
    idle = red["window_s"] - red["busy_s"]
    assert sum(g[1] for g in gaps) == pytest.approx(idle, abs=1e-6)


def test_window_must_be_annotated(recorded):
    with pytest.raises(ValueError):
        window_of(recorded[0], "bench.absent")
