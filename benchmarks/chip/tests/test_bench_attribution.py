"""Idle attribution and CPU self time (``harness.attribution``), on a
profiler trace recorded here on the CPU: two worker threads run the
program's spans (``repro.obs``, an enabled tracer), a third thread's host
annotations named ``op.*`` stand in for device operations, and the main
thread holds the benchmark's ``bench.*`` spans."""
import copy
import threading
import time
from types import SimpleNamespace

import pytest

from harness import attribution
from harness.trace import TraceEvents, find_xplane, reduce_trace, window_of

GAP = 0.08      # seconds of each idle stretch
BUSY = 0.06     # seconds of the stand-in device operation


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The trace, its host lines and the program's events."""
    import jax  # noqa: PLC0415

    from repro.obs import tracer as obs  # noqa: PLC0415
    d = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    both_open = threading.Barrier(2)
    phase_done = threading.Barrier(3)
    op_done = threading.Event()

    def worker(name):
        with obs.span("campaign.worker"):
            both_open.wait()
            with obs.span(name):
                time.sleep(GAP)
            phase_done.wait()
            with obs.span("wave.result_wait"):
                op_done.wait()

    def device():
        phase_done.wait()
        with jax.profiler.TraceAnnotation("op.kernel"):
            time.sleep(BUSY)
        op_done.set()

    tr = obs.Tracer(enabled=True)
    prev = obs.set_tracer(tr)
    jax.profiler.start_trace(str(d), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            with jax.profiler.TraceAnnotation("op.warmup"):
                time.sleep(BUSY)
            with jax.profiler.TraceAnnotation("bench.setup"):
                time.sleep(GAP)
            with jax.profiler.TraceAnnotation("bench.campaign"):
                threads = [threading.Thread(target=worker, args=(n,))
                           for n in ("wave.lower", "wave.pack")]
                threads.append(threading.Thread(target=device))
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
    finally:
        jax.profiler.stop_trace()
        obs.set_tracer(prev)
    path = find_xplane(d)
    events = TraceEvents(path, device_plane=r"^/host:CPU$", op_line="python",
                         op_name=lambda n: n.startswith("op."))
    return SimpleNamespace(events=events,
                           lines=attribution.host_lines(path),
                           program=tr.events())


def _attribute(rec):
    window = window_of(rec.events, "bench.traced")
    return window, attribution.idle_attribution(rec.events, rec.lines,
                                                window)


def test_idle_attribution_on_recorded_trace(recorded):
    window, att = _attribute(recorded)
    red = reduce_trace(recorded.events, window)
    idle = red["window_s"] - red["busy_s"]
    by = att["by_span"]
    # every idle instant is credited once
    assert att["idle_s"] == pytest.approx(idle, rel=1e-9)
    assert sum(by.values()) == pytest.approx(idle, rel=1e-9)
    # the annotations' own lengths on the trace's clock
    length = {n: (e - s) / 1e9 for line in recorded.lines
              for n, s, e in line}
    # two workers open all through: each gets half of its span
    assert by["wave.lower"] == pytest.approx(length["wave.lower"] / 2,
                                             rel=1e-6)
    assert by["wave.pack"] == pytest.approx(length["wave.pack"] / 2,
                                            rel=1e-6)
    # no worker open: the innermost benchmark span, as the breakdown does
    assert by["bench.setup"] == pytest.approx(length["bench.setup"],
                                              rel=1e-6)
    assert length["bench.setup"] >= GAP
    # the device was busy while the workers waited for its result
    assert by.get("wave.result_wait", 0.0) < 0.2 * BUSY
    assert "op.kernel" not in by


def test_idle_share_reads_named_spans(recorded):
    _, att = _attribute(recorded)
    share = attribution.idle_share(att, ("wave.lower", "wave.pack"))
    want = 100 * (att["by_span"]["wave.lower"]
                  + att["by_span"]["wave.pack"]) / att["idle_s"]
    assert share == pytest.approx(want)
    assert attribution.idle_share(None, ("wave.lower",)) is None


def test_reduction_unchanged_by_program_annotations(recorded):
    """``reduce_trace`` and the readers of its output give the same values
    whether or not the trace holds the program's annotations."""
    from harness.readers import device_idle  # noqa: PLC0415
    events = recorded.events
    window = window_of(events, "bench.traced")
    bare = copy.copy(events)
    bare.host = [h for h in events.host
                 if h[0].startswith(("bench.", "op."))]
    assert len(bare.host) < len(events.host)
    with_spans = reduce_trace(events, window)
    without = reduce_trace(bare, window)
    assert with_spans == without
    assert device_idle({"trace": with_spans}) == \
        device_idle({"trace": without})


def _fake(device_ops, bench):
    return SimpleNamespace(device_ops=device_ops, host=bench)


def test_idle_attribution_exact_split():
    """By hand: idle [0,10], [20,50], [60,110] of a 110 ns window."""
    a = [("campaign.worker", 0, 80), ("wave.lower", 0, 30),
         ("wave.pack", 60, 80)]
    b = [("campaign.worker", 25, 100), ("wave.extract", 25, 100)]
    main = [("bench.traced", 0, 110), ("bench.x", 90, 110)]
    ops = {"dev0": [("op", 10, 20), ("op", 50, 60)]}
    att = attribution.idle_attribution(
        _fake(ops, main), [a, b, main], (0, 110))
    want = {"wave.lower": 10 + 5 + 2.5, "wave.extract": 2.5 + 10 + 10 + 20,
            "campaign.worker": 10, "wave.pack": 10, "bench.x": 10}
    assert att["by_span"] == pytest.approx(
        {k: v / 1e9 for k, v in want.items()})
    assert att["idle_s"] == pytest.approx(90 / 1e9)
    # a second device busy all through halves every credit: idle is the
    # mean over the devices that ran an operation, as reduce_trace's
    ops["dev1"] = [("op", 0, 110)]
    att2 = attribution.idle_attribution(
        _fake(ops, main), [a, b, main], (0, 110))
    assert att2["idle_s"] == pytest.approx(45 / 1e9)
    assert att2["by_span"] == pytest.approx(
        {k: v / 2e9 for k, v in want.items()})
    assert attribution.idle_attribution(_fake({}, main), [main],
                                        (0, 110)) is None


def _ev(name, t0, dur, cpu, tid=1, **args):
    e = {"ph": "X", "name": name, "t0": t0, "dur": dur, "tid": tid,
         "args": args or None}
    if cpu is not None:
        e["cpu"] = cpu
    return e


def test_cpu_self_time_subtracts_direct_children():
    events = [_ev("wave.pack", 0, 100, 80),
              _ev("wave.dispatch", 10, 20, 15, steps=64),
              _ev("wave.dispatch_lock_wait", 12, 5, 4),
              _ev("wave.result_wait", 50, 10, 5),
              _ev("wave.lower", 0, 50, 40, tid=2),
              _ev("wave.extract", 0, 50, 30, tid=3)]
    got = attribution.cpu_self_time_s(events, ("wave.pack", "wave.lower"))
    assert got == pytest.approx((80 - 15 - 5 + 40) / 1e9)
    assert attribution.cpu_self_time_s(events, ("absent",)) is None
    # a trace from a program without per-span CPU time: nothing to read
    old = [dict(e) for e in events]
    for e in old:
        e.pop("cpu")
    assert attribution.cpu_self_time_s(old, ("wave.pack",)) is None
    assert attribution.span_arg_sum(events, "wave.dispatch", "steps") == 64
    assert attribution.span_arg_sum(events, "wave.dispatch", "rows") is None


def test_cpu_self_time_on_recorded_spans(recorded):
    """Sleeping spans take almost no CPU; ``cpu`` never exceeds wall."""
    got = attribution.cpu_self_time_s(recorded.program,
                                      ("wave.lower", "wave.pack"))
    assert 0 <= got < 0.2 * GAP
    for e in recorded.program:
        assert 0 <= e["cpu"] <= e["dur"] + 1e6
