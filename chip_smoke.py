"""Smoke run of the main path on a TPU: characterize -> export -> serve,
plus corpus ground truth, through the device kernels.

One chip (the default) runs three phases, each checked against a host
reference computed in this same process:

  (a) campaign  - a cold ``Campaign`` over the full TEST_ISA on every SIM
                  uarch (wave kernel on the chip), exported to XML and
                  byte-compared with a numpy-backend campaign;
  (b) corpus    - a seed-0 corpus of 2,048 blocks per uarch evaluated with
                  ``evaluate_corpus`` (one 2,048-block wave per uarch);
                  its rows must equal a numpy-backend run scored by the
                  single-block reference predictor;
  (c) serving   - ``start_server`` over (a)'s XML; ``predict_batch``
                  requests of 64 distinct blocks on both wires, compared
                  with the single-block ``core/predictor.py``.

``--chips 4`` runs only the mesh phase: (a)'s campaign placed across the
four chips plus one four-device wave of 512 lanes, both bit-identical to
numpy, with lanes on every device.

Every phase prints its counters and smoke wall times (set-up plus work on
a shared host: not benchmark numbers) on lines of its own; the last line
is ``{"ok": true, "device": {...}}`` and appears only if every check
held.  Without a TPU the script exits non-zero before any phase.  Outputs
go to chiprun_out/chip_smoke/.  JAX's compile cache is the program's own
(``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` in the checkout), so a
second run shows compiles served from it.

Run:  python chip_smoke.py [--chips 4]
"""
import argparse
import json
import random
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"
WAVE_BLOCKS = 2048        # README's --wave-width: one wave per uarch
SERVE_BLOCKS = 64         # per predict_batch request (>= MIN_DEVICE_BLOCKS)
MESH_LANES = 512


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class CompileMeter:
    """Process-wide XLA compile count and seconds (a persistent-cache hit
    counts, at its load time), and the persistent cache's hits and writes,
    from jax's monitoring events."""

    def __init__(self):
        import jax  # noqa: PLC0415
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":  # = written
            self.cache_writes += 1

    def snapshot(self) -> tuple:
        return (self.compiles, self.seconds, self.cache_hits,
                self.cache_writes)

    def since(self, snap: tuple) -> dict:
        n, s, h, w = snap
        return {"xla_compiles": self.compiles - n,
                "xla_compile_s": round(self.seconds - s, 3),
                "compile_cache_hits": self.cache_hits - h,
                "compile_cache_writes": self.cache_writes - w}


def _fresh(path: Path) -> Path:
    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True)
    return path


def _same_counters(a, b) -> bool:
    return (len(a) == len(b)
            and all(x.cycles == y.cycles and x.port_uops == y.port_uops
                    for x, y in zip(a, b)))


def _check_sim_machine(tag: str, machine) -> dict:
    """The machine ran its waves on the chip's jax kernel, undegraded."""
    batch = machine.batch_machine()
    check(batch and batch.backend == "jax",
          f"{tag}: wave backend is {batch and batch.backend!r}, not jax")
    st = machine.device_stats()
    check(st.get("kernel_calls", 0) > 0, f"{tag}: no device kernel call")
    check(not machine.degraded_stats(),
          f"{tag}: degraded {machine.degraded_stats()}")
    return st


def _campaign(out: Path, isa, machines):
    from repro.core.engine import Campaign  # noqa: PLC0415
    t0 = time.perf_counter()
    res = Campaign(cache_dir=_fresh(out)).run(machines, isa)
    dt = time.perf_counter() - t0
    check(not res.quarantine, f"campaign quarantined {res.quarantine}")
    return res, dt


def _numpy_reference_xml(out: Path, isa) -> dict:
    from repro.core import model_io  # noqa: PLC0415
    from repro.core.simulator import SimMachine  # noqa: PLC0415
    from repro.core.uarch import SIM_UARCHES  # noqa: PLC0415
    machines = [SimMachine(ua, isa, backend="numpy")
                for ua in SIM_UARCHES.values()]
    res, dt = _campaign(out, isa, machines)
    log("reference", what="numpy campaign", smoke_wall_s=round(dt, 3))
    return {n: model_io.to_xml(m, isa) for n, m in res.models.items()}


def phase_campaign(out: Path, meter: CompileMeter) -> Path:
    """(a) cold campaign on the chip, XML byte-compared with numpy."""
    from repro.core import model_io  # noqa: PLC0415
    from repro.core.isa import TEST_ISA  # noqa: PLC0415
    from repro.core.simulator import SimMachine  # noqa: PLC0415
    from repro.core.uarch import SIM_UARCHES  # noqa: PLC0415

    snap = meter.snapshot()
    machines = [SimMachine(ua, TEST_ISA) for ua in SIM_UARCHES.values()]
    res, dt = _campaign(out / "meas_cache", TEST_ISA, machines)
    log("campaign", uarches=len(machines),
        instructions=sum(len(m.instructions) for m in res.models.values()),
        mean_wave_width=round(res.mean_wave_width, 1),
        smoke_wall_s=round(dt, 3), **meter.since(snap))
    want = _numpy_reference_xml(out / "meas_cache_numpy", TEST_ISA)
    models_dir = _fresh(out / "models")
    for m in machines:
        st = _check_sim_machine(f"campaign {m.name}", m)
        xml = model_io.to_xml(res.models[m.name], TEST_ISA)
        check(xml == want[m.name],
              f"campaign {m.name}: XML differs from the numpy reference")
        (models_dir / f"{m.name}.xml").write_text(xml)
        log("campaign", uarch=m.name, xml_identical=True,
            xml_bytes=len(xml), kernel_calls=st["kernel_calls"],
            wave_compiles=st["compiles"], buckets=len(st["buckets"]),
            degraded=0, quarantined=0)
    return models_dir


def phase_corpus(out: Path, models_dir: Path, meter: CompileMeter) -> list:
    """(b) corpus ground truth on the chip, rows equal to numpy's."""
    from repro.core import model_io  # noqa: PLC0415
    from repro.core.isa import TEST_ISA  # noqa: PLC0415
    from repro.core.predictor import predict  # noqa: PLC0415
    from repro.corpus.evaluate import evaluate_corpus  # noqa: PLC0415
    from repro.corpus.generate import (  # noqa: PLC0415
        CorpusSpec, generate_corpus)

    models = {p.stem: model_io.load_xml(p.read_text())
              for p in sorted(models_dir.glob("*.xml"))}
    corpus = _fresh(out / "corpus")
    manifest = generate_corpus(corpus, CorpusSpec(
        seed=0, blocks_per_uarch=WAVE_BLOCKS, shard_size=WAVE_BLOCKS))
    snap = meter.snapshot()
    t0 = time.perf_counter()
    got = evaluate_corpus(corpus, out_dir=_fresh(out / "rows_chip"),
                          wave_width=WAVE_BLOCKS, resume=False,
                          models=models)
    dt = time.perf_counter() - t0
    log("corpus", blocks=got["wave_stats"]["blocks"],
        waves=got["wave_stats"]["waves"],
        max_wave_width=got["wave_stats"]["max_wave_width"],
        smoke_wall_s=round(dt, 3), **meter.since(snap))

    def reference_predict(uarch, blocks):
        return [predict(models[uarch], TEST_ISA, b).cycles for b in blocks]

    t0 = time.perf_counter()
    ref = evaluate_corpus(corpus, backend="numpy",
                          out_dir=_fresh(out / "rows_numpy"),
                          wave_width=WAVE_BLOCKS, resume=False,
                          models=models, predict_fn=reference_predict)
    log("reference", what="numpy corpus + single-block predictor",
        smoke_wall_s=round(time.perf_counter() - t0, 3))
    check(got["wave_stats"]["max_wave_width"] >= WAVE_BLOCKS,
          f"corpus: no {WAVE_BLOCKS}-block wave formed")
    eng = got["engine_stats"]
    check(eng.get("quarantined", 0) == 0 and
          eng.get("degraded_chunks", 0) == 0,
          f"corpus: engine quarantined/degraded {eng}")
    for ua in sorted(manifest["spec"]["uarches"]):
        check(got["uarches"][ua] == ref["uarches"][ua],
              f"corpus {ua}: rows differ from the numpy reference")
        be = got["backends"][ua]
        dev, pred = be["device"], be["predictor"]
        check(dev.get("backend") == "jax" and dev["kernel_calls"] > 0,
              f"corpus {ua}: wave kernel not on the chip {dev}")
        check(not be["degraded"], f"corpus {ua}: degraded {be['degraded']}")
        check(pred["device_waves"] > 0 and pred["device_fallbacks"] == 0,
              f"corpus {ua}: predictor kernel not on the chip {pred}")
        log("corpus", uarch=ua, rows=len(got["uarches"][ua]),
            rows_identical=True, kernel_calls=dev["kernel_calls"],
            wave_compiles=dev["compiles"],
            predictor_device_waves=pred["device_waves"],
            predictor_compiles=pred["device_compiles"],
            degraded=0, device_fallbacks=0)
    return [(ua, rec["block"]) for ua in sorted(got["uarches"])
            for rec in got["uarches"][ua]]


def phase_serving(models_dir: Path, blocks: list,
                  meter: CompileMeter) -> None:
    """(c) served predictions on both wires, equal to the single-block
    reference predictor, answered by the device kernel."""
    from repro.core import model_io  # noqa: PLC0415
    from repro.core.isa import TEST_ISA  # noqa: PLC0415
    from repro.core.predictor import predict  # noqa: PLC0415
    from repro.service.client import ServiceClient  # noqa: PLC0415
    from repro.service.protocol import (  # noqa: PLC0415
        parse_block, prediction_to_dict)
    from repro.service.server import start_server  # noqa: PLC0415

    models = {p.stem: model_io.load_xml(p.read_text())
              for p in sorted(models_dir.glob("*.xml"))}
    by_uarch: dict = {}
    for ua, text in blocks:
        if text not in by_uarch.setdefault(ua, []):
            by_uarch[ua].append(text)
    snap = meter.snapshot()
    t0 = time.perf_counter()
    requests = 0
    with start_server(models_dir) as srv:
        for k, wire in enumerate(("binary", "json")):
            client = ServiceClient(srv.host, srv.port, wire=wire)
            try:
                check(client.wire == wire, f"serving: negotiated "
                      f"{client.wire!r}, asked for {wire!r}")
                waves0 = (client.stats().get("predictor") or {}).get(
                    "device_waves", 0)
                for ua in sorted(models):
                    # distinct blocks per request: no cache can answer it
                    texts = by_uarch[ua][k * SERVE_BLOCKS:
                                         (k + 1) * SERVE_BLOCKS]
                    check(len(texts) == SERVE_BLOCKS,
                          f"serving {ua}: too few distinct blocks")
                    envs = client.predict_batch(ua, texts)
                    requests += 1
                    for text, env in zip(texts, envs):
                        want = prediction_to_dict(predict(
                            models[ua], TEST_ISA, parse_block(text)))
                        check(env.get("ok") and env["result"] == want,
                              f"serving {ua} ({wire}): {text!r} -> {env}")
                pred = client.stats()["predictor"]
            finally:
                client.close()
            check(pred["backend"] == "jax", f"serving: predictor {pred}")
            check(pred["device_waves"] - waves0 == len(models),
                  f"serving ({wire}): {pred['device_waves'] - waves0} "
                  f"device waves for {len(models)} requests")
            check(pred["device_fallbacks"] == 0,
                  f"serving: {pred['device_fallbacks']} device fallbacks")
            log("serving", wire=wire, requests=len(models),
                blocks_per_request=SERVE_BLOCKS, payloads_identical=True,
                device_waves=pred["device_waves"],
                predictor_compiles=pred["device_compiles"],
                device_fallbacks=0)
    log("serving", requests=requests,
        smoke_wall_s=round(time.perf_counter() - t0, 3), **meter.since(snap))


def phase_mesh(out: Path, meter: CompileMeter) -> None:
    """Four chips: (a)'s campaign placed across the chips, plus one
    four-device wave, both bit-identical to numpy."""
    import jax  # noqa: PLC0415
    from repro.core import model_io  # noqa: PLC0415
    from repro.core.batch_sim import BatchSimMachine  # noqa: PLC0415
    from repro.core.isa import TEST_ISA  # noqa: PLC0415
    from repro.core.machine import RegPool, independent_seq  # noqa: PLC0415
    from repro.core.simulator import SimMachine  # noqa: PLC0415
    from repro.core.uarch import SIM_SKL, SIM_UARCHES  # noqa: PLC0415

    all_ids = {d.id for d in jax.devices()}
    snap = meter.snapshot()
    machines = [SimMachine(ua, TEST_ISA) for ua in SIM_UARCHES.values()]
    res, dt = _campaign(out / "meas_cache", TEST_ISA, machines)
    log("mesh", what="campaign", smoke_wall_s=round(dt, 3),
        **meter.since(snap))
    want = _numpy_reference_xml(out / "meas_cache_numpy", TEST_ISA)
    used = set()
    for m in machines:
        st = _check_sim_machine(f"mesh campaign {m.name}", m)
        check(model_io.to_xml(res.models[m.name], TEST_ISA) == want[m.name],
              f"mesh campaign {m.name}: XML differs from numpy")
        lanes = {d: c["lanes"] for d, c in st["per_device"].items()}
        used |= {d for d, n in lanes.items() if n > 0}
        log("mesh", uarch=m.name, xml_identical=True, devices=st["devices"],
            mesh=st["mesh"], kernel_calls=st["kernel_calls"],
            lanes_per_device=json.dumps(lanes, sort_keys=True))
    check(used == all_ids, f"mesh campaign: lanes only on devices {used}")

    rng = random.Random(0)
    specs = ["ADD_R64_R64", "IMUL_R64_R64", "MOV_R64_R64", "SHLD_R64_R64_I8",
             "PADDD_X_X", "MOV_R64_M64", "ADC_R64_R64", "MULPS_X_X",
             "DIV_R64", "AESDEC_X_X"]
    codes = []
    for _ in range(MESH_LANES // 2):
        body = independent_seq(TEST_ISA[rng.choice(specs)], RegPool(),
                               rng.randint(4, 12))
        codes += [body * 10, body * 110]
    ref = BatchSimMachine(SIM_SKL, TEST_ISA, backend="numpy").run_batch(codes)
    m4 = BatchSimMachine(SIM_SKL, TEST_ISA, backend="jax", devices=4)
    snap = meter.snapshot()
    t0 = time.perf_counter()
    got = m4.run_batch(codes)
    dt = time.perf_counter() - t0
    check(_same_counters(got, ref), "mesh wave: Counters differ from numpy")
    st = m4.device_stats()
    lanes = {d: c["lanes"] for d, c in st["per_device"].items()}
    check(st["mesh"] and set(st["devices"]) == all_ids,
          f"mesh wave: placed on {st['devices']}")
    check(all(lanes.get(d, 0) > 0 for d in all_ids),
          f"mesh wave: lanes per device {lanes}")
    check(not m4.degraded_stats(), f"mesh wave: degraded "
          f"{m4.degraded_stats()}")
    log("mesh", what="wave", lanes=len(codes), counters_identical=True,
        kernel_calls=st["kernel_calls"],
        lanes_per_device=json.dumps(lanes, sort_keys=True),
        smoke_wall_s=round(dt, 3), **meter.since(snap))


def run(chips: int, out: Path = OUT) -> None:
    """The phases for ``chips`` chips (raises :class:`SmokeFailure`)."""
    from repro.core.device_mesh import enable_compile_cache  # noqa: PLC0415
    meter = CompileMeter()
    cache = enable_compile_cache()
    log("setup", compile_cache=cache,
        compile_cache_entries=(len(list(Path(cache).iterdir()))
                               if cache and Path(cache).is_dir() else 0))
    out.mkdir(parents=True, exist_ok=True)
    t0 = time.perf_counter()
    if chips == 4:
        phase_mesh(out / "mesh", meter)
    else:
        models_dir = phase_campaign(out / "campaign", meter)
        blocks = phase_corpus(out / "corpus", models_dir, meter)
        phase_serving(models_dir, blocks, meter)
    log("total", smoke_wall_s=round(time.perf_counter() - t0, 3),
        **meter.since((0, 0.0, 0, 0)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: campaign, corpus and serving phases; "
                         "4: the mesh phase only")
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax platform is "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the program is not here ({e})", file=sys.stderr)
        return 1
    log("setup", platform=devices[0].platform,
        device_kind=devices[0].device_kind, device_count=len(devices),
        jax=jax.__version__)
    try:
        run(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
