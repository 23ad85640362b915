"""Observability layer: tracer semantics, exporters, metrics registry,
legacy stats-shape pinning, service trace ids / access log, and the
"tracing must not perturb results" bit-identity guarantee.

The load-bearing guarantees:
  * disabled tracing is a shared stateless no-op (same singleton back from
    every call site — no allocation on the off path);
  * spans nest per thread and are reentrant across the Campaign pool;
  * the Chrome trace-event export is schema-valid (ph/ts/dur/pid/tid/args,
    thread-name metadata, counter tracks);
  * an enabled tracer's spans are profiler annotations on their own
    thread's host line, and carry thread CPU time beside wall time;
  * characterize with tracing ON produces byte-identical XML to the
    committed model artifact;
  * the legacy stats dict shapes (EngineStats.as_dict, server stats) are
    pinned while the metrics registry is the source of truth.
"""
import json
import logging
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import pytest

from repro.core import model_io
from repro.core.characterize import characterize
from repro.core.engine import Campaign, MeasurementEngine
from repro.core.isa import TEST_ISA
from repro.core.simulator import SimMachine
from repro.core.uarch import SIM_UARCHES
from repro.obs import export, metrics, tracer
from repro.obs.tracer import NULL_SPAN, Tracer, set_tracer

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def traced():
    """Install a fresh enabled tracer; restore the previous one after."""
    tr = Tracer(enabled=True)
    prev = set_tracer(tr)
    yield tr
    set_tracer(prev)


@pytest.fixture
def disabled():
    tr = Tracer(enabled=False)
    prev = set_tracer(tr)
    yield tr
    set_tracer(prev)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_disabled_is_shared_noop(disabled):
    # every call site gets the same stateless singleton: no allocation
    sp = tracer.span("x", wave=3)
    assert sp is NULL_SPAN
    assert tracer.span("y") is sp
    with sp as inner:
        assert inner is sp
        inner.set(k=1)  # no-op, chainable
    tracer.instant("i", a=1)
    tracer.counter("c", 42)
    assert disabled.events() == []
    assert not tracer.enabled()


def test_disabled_wait_lock_still_locks(disabled):
    lock = threading.Lock()
    with tracer.wait_lock(lock, "w"):
        assert lock.locked()
    assert not lock.locked()
    with tracer.wait_lock(None, "w"):  # no lock configured: pure no-op
        pass
    assert disabled.events() == []


def test_span_nesting_and_attrs(traced):
    with tracer.span("outer", a=1) as out_sp:
        with tracer.span("inner") as in_sp:
            in_sp.set(b=2)
        out_sp.set(c=3)
    evs = traced.events()
    names = [e["name"] for e in evs]
    assert names == ["inner", "outer"]  # children close first
    inner, outer = evs
    assert inner["ph"] == outer["ph"] == "X"
    assert inner["args"] == {"b": 2}
    assert outer["args"] == {"a": 1, "c": 3}
    assert inner["t0"] >= outer["t0"]
    assert inner["dur"] <= outer["dur"]


def test_trace_id_inheritance(traced):
    with tracer.span("request", trace_id="abc123"):
        with tracer.span("child"):
            with tracer.span("grandchild", own=1):
                pass
    by_name = {e["name"]: e for e in traced.events()}
    assert by_name["child"]["args"] == {"trace_id": "abc123"}
    assert by_name["grandchild"]["args"] == {"own": 1, "trace_id": "abc123"}


def test_reentrant_across_threads(traced):
    # Campaign-style pool: per-thread stacks must not interleave
    def work(i):
        with tracer.span("outer", worker=i):
            with tracer.span("inner", worker=i):
                pass

    with ThreadPoolExecutor(max_workers=4) as pool:
        list(pool.map(work, range(16)))
    evs = traced.events()
    assert len(evs) == 32
    for ev in evs:
        assert ev["ph"] == "X" and ev["dur"] >= 0
    # every worker's inner span is attributed to the same thread as its
    # outer span
    pairs: dict = {}
    for ev in evs:
        pairs.setdefault(ev["args"]["worker"], set()).add(ev["tid"])
    assert all(len(tids) == 1 for tids in pairs.values())
    assert set(traced.thread_names()) == {e["tid"] for e in evs}


def test_wait_lock_measures_contention(traced):
    lock = threading.Lock()
    lock.acquire()
    t = threading.Timer(0.03, lock.release)
    t.start()
    with tracer.wait_lock(lock, "wave.lock_wait"):
        pass
    t.join()
    (ev,) = traced.events()
    assert ev["name"] == "wave.lock_wait"
    assert ev["dur"] >= 20e6  # waited >= 20ms, in ns
    assert ev["cpu"] < ev["dur"] / 2   # blocked, not computing


def _busy(seconds):
    t_end = time.perf_counter() + seconds
    x = 0
    while time.perf_counter() < t_end:
        x += 1
    return x


def test_span_records_thread_cpu_time(traced):
    with tracer.span("busy"):
        _busy(0.05)
    with tracer.span("sleep"):
        time.sleep(0.05)
    by = {e["name"]: e for e in traced.events()}
    busy, sleep = by["busy"], by["sleep"]
    # CPU time never exceeds wall (1 ms for the clocks' own granularity);
    # a busy loop is mostly CPU even if the scheduler preempts it, a
    # sleep is almost none
    assert 0.5 * busy["dur"] <= busy["cpu"] <= busy["dur"] + 1e6
    assert sleep["dur"] >= 45e6
    assert sleep["cpu"] <= 0.1 * sleep["dur"]


def test_spans_are_profiler_annotations_on_their_threads(traced, tmp_path):
    """Each thread's spans land on that thread's host line of the
    profiler trace, nested as they ran; three threads, three lines."""
    import glob

    import jax
    from jax.profiler import ProfileData

    barrier = threading.Barrier(3)

    def work(i):
        barrier.wait()
        with tracer.span(f"outer.{i}"):
            with tracer.span(f"inner.{i}"):
                time.sleep(0.01)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    (host,) = [p for p in ProfileData.from_file(path).planes
               if p.name == "/host:CPU"]
    lines = []
    for line in host.lines:
        evs = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
               for ev in line.events
               if ev.name.startswith(("outer.", "inner."))}
        if evs:
            lines.append(evs)
    assert len(lines) == 3
    for i in range(3):
        (line,) = [evs for evs in lines if f"outer.{i}" in evs]
        assert set(line) == {f"outer.{i}", f"inner.{i}"}
        (a, b), (c, d) = line[f"outer.{i}"], line[f"inner.{i}"]
        assert a <= c < d <= b
        assert d - c >= 9e6
    # the tracer's own events are unchanged by the profiler
    assert sorted(e["name"] for e in traced.events()) == sorted(
        f"{k}.{i}" for i in range(3) for k in ("inner", "outer"))


def test_enabled_tracer_without_jax(traced, monkeypatch):
    """Without jax the tracer records its events all the same."""
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    monkeypatch.setattr(tracer, "_ANNOTATION", ())
    with tracer.span("a", k=1):
        with tracer.wait_lock(threading.Lock(), "w"):
            pass
    assert tracer._ANNOTATION is None
    evs = traced.events()
    assert [e["name"] for e in evs] == ["w", "a"]
    assert all(e["cpu"] >= 0 for e in evs)


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def _populate(tr):
    with tracer.span("scheduler.run", plans=2):
        with tracer.span("wave.run_batch", lanes=4):
            pass
    tracer.counter("scheduler.wave_width", 4)
    tracer.instant("mesh.partition", devices=0)


def test_chrome_trace_schema(traced):
    _populate(traced)
    doc = export.chrome_trace(traced, process_name="repro-test")
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in evs if e["ph"] == "M"]
    assert {"ph": "M", "name": "process_name", "pid": traced.pid, "tid": 0,
            "args": {"name": "repro-test"}} in meta
    tnames = {e["tid"]: e["args"]["name"] for e in meta
              if e["name"] == "thread_name"}
    assert threading.current_thread().name in tnames.values()
    for ev in evs:
        assert set(ev) >= {"ph", "name", "pid", "tid", "args"}
        assert ev["pid"] == traced.pid
        if ev["ph"] != "M":
            assert ev["ts"] >= 0
    complete = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert "dur" in complete["scheduler.run"]
    assert complete["scheduler.run"]["args"] == {"plans": 2}
    # every event sits on its thread's track
    assert tnames[complete["wave.run_batch"]["tid"]] == \
        threading.current_thread().name
    (cnt,) = [e for e in evs if e["ph"] == "C"]
    assert cnt["args"] == {"value": 4}
    (inst,) = [e for e in evs if e["ph"] == "i"]
    assert inst["s"] == "t"


def test_exporters_roundtrip(traced, tmp_path):
    _populate(traced)
    cpath = export.write_chrome_trace(tmp_path / "t.trace.json", traced)
    jpath = export.write_jsonl(tmp_path / "t.trace.jsonl", traced)
    json.loads(Path(cpath).read_text())  # valid single-document JSON
    a = export.load_events(cpath)
    b = export.load_events(jpath)
    key = lambda e: (e["name"], e["ph"], round(e["ts_us"], 3))  # noqa: E731
    assert sorted(map(key, a)) == sorted(map(key, b))
    by_name = {e["name"]: e for e in a}
    assert by_name["wave.run_batch"]["tid_name"] == \
        threading.current_thread().name
    assert by_name["wave.run_batch"]["dur_us"] >= 0


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_registry_instruments():
    reg = metrics.MetricsRegistry()
    reg.counter("c").inc(3)
    reg.gauge("g").set(1.5)
    h = reg.histogram("h", keep=8)
    for v in range(16):
        h.observe(float(v))
    assert reg.counter("c") is reg.counter("c")  # get-or-create
    with pytest.raises(TypeError):
        reg.gauge("c")
    snap = reg.snapshot()
    assert snap["c"] == {"type": "counter", "value": 3}
    assert snap["g"] == {"type": "gauge", "value": 1.5}
    hs = snap["h"]
    assert hs["type"] == "histogram"
    assert hs["count"] == 16 and hs["min"] == 0.0 and hs["max"] == 15.0
    # reservoir keeps the newest 8, but count/sum/min/max stay exact
    assert 8.0 <= hs["p50"] <= 15.0
    assert reg.value("c") == 3


def test_engine_stats_legacy_shape():
    m = SimMachine(SIM_UARCHES["sim_skl"], TEST_ISA)
    engine = MeasurementEngine(m)
    model = characterize(engine, TEST_ISA, ["ADD_R64_R64"])
    assert model.instructions
    stats = engine.stats.as_dict()
    assert list(stats) == ["requests", "cache_hits", "dedup_hits",
                           "executions", "machine_runs", "batches",
                           "evictions", "lowering_hits", "lowering_misses",
                           "lowering_evictions", "quarantined",
                           "bisect_retries", "degraded_chunks",
                           "hit_rate", "device"]
    assert stats["requests"] > 0
    # resilience counters are zero on a clean run (and as_dict drops the
    # quarantine/degraded detail maps entirely when empty)
    assert stats["quarantined"] == 0 and stats["degraded_chunks"] == 0
    assert "quarantine" not in stats and "degraded" not in stats
    # and the canonical registry carries the same numbers
    reg = metrics.MetricsRegistry()
    metrics.absorb_engine_stats(reg, stats)
    assert reg.value("engine.requests") == stats["requests"]
    assert metrics.legacy_engine_dict(reg) == {
        k: v for k, v in stats.items() if k != "device"}


# ---------------------------------------------------------------------------
# tracing must not perturb results
# ---------------------------------------------------------------------------


def test_characterize_traced_xml_bit_identical(traced):
    """Full-ISA characterize with tracing ON is byte-identical to an
    untraced run (and to the exported model artifact when one exists),
    and the trace contains the expected spans."""
    prev = set_tracer(Tracer(enabled=False))
    try:
        m0 = SimMachine(SIM_UARCHES["sim_skl"], TEST_ISA)
        want = model_io.to_xml(
            characterize(MeasurementEngine(m0), TEST_ISA), TEST_ISA)
    finally:
        set_tracer(traced)
    m = SimMachine(SIM_UARCHES["sim_skl"], TEST_ISA)
    got = model_io.to_xml(
        characterize(MeasurementEngine(m), TEST_ISA), TEST_ISA)
    assert got == want
    artifact = REPO / "experiments" / "models" / "sim_skl.xml"
    if artifact.exists():  # export_models.py output is local, not tracked
        assert got == artifact.read_text()
    names = {e["name"] for e in traced.events()}
    assert names >= {"characterize", "scheduler.run", "scheduler.drain",
                     "scheduler.execute", "engine.submit",
                     "engine.cache_probe", "engine.miss_wave",
                     "wave.run_batch", "wave.lower", "wave.pack",
                     "wave.kernel", "wave.extract"}


def test_campaign_worker_spans(traced):
    machines = [SimMachine(ua, TEST_ISA) for ua in SIM_UARCHES.values()]
    Campaign(instr_names=["ADD_R64_R64", "MUL_R64"]).run(machines, TEST_ISA)
    evs = traced.events()
    workers = [e for e in evs if e["name"] == "campaign.worker"]
    assert len(workers) == len(machines)
    assert {w["args"]["uarch"] for w in workers} == set(SIM_UARCHES)
    (run,) = [e for e in evs if e["name"] == "campaign.run"]
    assert run["args"]["machines"] == len(machines)


# ---------------------------------------------------------------------------
# wave report
# ---------------------------------------------------------------------------


def test_wave_report_attribution(traced, tmp_path):
    from repro.analysis.wave_report import format_wave_report, wave_report

    m = SimMachine(SIM_UARCHES["sim_hsw"], TEST_ISA)
    characterize(MeasurementEngine(m), TEST_ISA,
                 ["ADD_R64_R64", "IMUL_R64_R64", "PADDD_X_X"])
    path = export.write_chrome_trace(tmp_path / "t.trace.json", traced)
    rep = wave_report(export.load_events(path))
    assert rep["waves"] > 0
    assert rep["stages"]["kernel"]["us"] > 0
    shares = [s["share"] for s in rep["stages"].values()]
    assert abs(sum(shares) + rep["lock_wait"]["share"] - 1.0) < 1e-9
    assert rep["bottleneck"].endswith(("-bound", "idle"))
    assert rep["top_waves"]
    text = format_wave_report(rep)
    assert "bottleneck" in text and "lock_wait" in text


def test_wave_report_device_imbalance():
    from repro.analysis import wave_report as wr

    # lock-bound wins over stage attribution when wait dominates
    rep2 = wr.wave_report([
        {"ph": "X", "name": "wave.kernel", "ts_us": 0.0, "dur_us": 100.0,
         "tid": 1, "tid_name": "", "args": {}},
        {"ph": "X", "name": "wave.lock_wait", "ts_us": 0.0, "dur_us": 100.0,
         "tid": 1, "tid_name": "", "args": {}}])
    assert rep2["bottleneck"] == "lock-bound"


# ---------------------------------------------------------------------------
# service: trace ids, access log, stats shapes
# ---------------------------------------------------------------------------

SERVICE_NAMES = ["ADD_R64_R64", "IMUL_R64_R64", "CMC", "ADC_R64_R64"]


@pytest.fixture(scope="module")
def obs_model_dir(tmp_path_factory):
    machines = [SimMachine(SIM_UARCHES["sim_skl"], TEST_ISA)]
    models = Campaign(instr_names=SERVICE_NAMES).run(machines,
                                                     TEST_ISA).models
    out = tmp_path_factory.mktemp("obs_models")
    for name, model in models.items():
        (out / f"{name}.xml").write_text(model_io.to_xml(model, TEST_ISA))
    return out


def _service(obs_model_dir, **kw):
    from repro.service.registry import ModelRegistry
    from repro.service.server import PredictionService

    return PredictionService(ModelRegistry(obs_model_dir), **kw)


BLOCK = [("ADD_R64_R64", {"op1": "R0", "op2": "R1"})]


def _instrs(pairs):
    from repro.core.simulator import Instr

    return [Instr(n, ops) for n, ops in pairs]


def test_trace_ids_in_responses(obs_model_dir):
    with _service(obs_model_dir) as svc:
        code = _instrs(BLOCK)
        r1 = svc.predict("sim_skl", code)
        r2 = svc.predict("sim_skl", code)
        assert r1["ok"] and r2["ok"]
        assert r1["trace_id"] != r2["trace_id"]
        assert len(r1["trace_id"]) == 16
        batch = svc.predict_batch("sim_skl", [code, code])
        tids = {b["trace_id"] for b in batch}
        assert len(tids) == 1  # one explicit batch = one trace id
        assert tids.isdisjoint({r1["trace_id"], r2["trace_id"]})


def test_access_log_and_slow_request(obs_model_dir, tmp_path, caplog):
    log = tmp_path / "access.jsonl"
    with _service(obs_model_dir, access_log=str(log),
                  slow_request_us=0.0) as svc:
        code = _instrs(BLOCK)
        with caplog.at_level(logging.WARNING, logger="repro.service"):
            r1 = svc.predict("sim_skl", code)     # miss
            r2 = svc.predict("sim_skl", code)     # cache hit
            svc.predict_batch("sim_skl", [code])
    recs = [json.loads(line) for line in log.read_text().splitlines()]
    assert len(recs) == 3
    for rec in recs:
        assert set(rec) == {"ts", "trace_id", "endpoint", "batch",
                            "cache_hits", "wall_us", "ok"}
        assert rec["ok"] is True
    assert recs[0]["trace_id"] == r1["trace_id"]
    assert recs[0]["cache_hits"] == 0
    assert recs[1]["trace_id"] == r2["trace_id"]
    assert recs[1]["cache_hits"] == 1
    assert recs[2]["endpoint"] == "predict_batch"
    # budget 0 => every request is over budget
    slow = [r for r in caplog.records if "slow request" in r.message]
    assert len(slow) >= 3
    assert r1["trace_id"] in "".join(r.getMessage() for r in slow)


def test_server_stats_legacy_shape_and_metrics(obs_model_dir):
    with _service(obs_model_dir) as svc:
        code = _instrs(BLOCK)
        svc.predict("sim_skl", code)
        svc.predict("sim_skl", code)
        stats = svc.stats()
        # pinned legacy shape
        assert set(stats) == {"uptime_s", "endpoints", "cache", "coalescer",
                              "registry"}
        ep = stats["endpoints"]["predict"]
        assert ep["requests"] == 2 and ep["errors"] == 0
        assert ep["p50_us"] > 0 and ep["p99_us"] >= ep["p50_us"]
        assert stats["cache"]["hits"] == 1
        # canonical snapshot carries the same numbers
        snap = svc.metrics()
        assert snap["server.endpoint.predict.count"]["value"] == 2
        assert snap["server.cache.hits"]["value"] == 1
        hist = snap["server.endpoint.predict.latency_s"]
        assert hist["type"] == "histogram" and hist["count"] == 2


def test_serve_group_spans_carry_trace_ids(obs_model_dir, traced):
    with _service(obs_model_dir) as svc:
        code = _instrs(BLOCK)
        res = svc.predict("sim_skl", code)
    evs = traced.events()
    sg = [e for e in evs if e["name"] == "server.serve_group"]
    assert sg and sg[0]["args"]["trace_id"] == res["trace_id"]
    # nested predictor spans inherited the request's trace id
    pb = [e for e in evs if e["name"] == "predict.batch"]
    assert pb and pb[0]["args"]["trace_id"] == res["trace_id"]
