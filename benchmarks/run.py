"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the harness convention plus
a human-readable table per benchmark. Paper mapping:

  table1_characterization   Table 1 — #instruction variants per uarch, tool
                            runtime, and measured-vs-legacy-analyzer
                            agreement (μops %, ports %) with planted
                            IACA-style bugs adjudicated by ground truth
  table_throughput_defs     §4.2 — instructions where the Intel (LP) and Fog
                            (measured) throughput definitions diverge
  fig_case_aesdec           §7.3.1 — AESDEC per-pair latency across uarches
  fig_case_shld             §7.3.2 — SHLD same-register effect
  fig_case_movq2dq          §7.3.3 — isolation-measurement fallacy
  table_multi_latency       §7.3.5 — instructions with pair-dependent latency
  table_zero_idioms         §7.3.6 — dependency-breaking idiom detection
  bench_lp                  §5.3.2 — LP solve rate
  bench_simulator           measurement-machine μop throughput
  bench_batch_sim           vectorized measurement substrate: scalar loop
                            vs NumPy vs jax batched backend, wave sweep +
                            thin-chunk scalar-crossover sweep (min_lanes)
  bench_backend_matrix      device-resident wave execution: numpy vs jax
                            (blocked scan) across wave widths, cold vs
                            warm lowering cache, with the kernel
                            recompile probe
  bench_trace_overhead      observability tax: numpy wave sweep with
                            repro.obs tracing off vs on, plus the analytic
                            disabled-overhead bound the CI <2% gate asserts
  bench_device_scaling      mesh-parallel wave execution: warm wave
                            throughput at 1/2/4 forced host devices
                            (subprocess — XLA_FLAGS must precede the jax
                            import), bit-identity + recompile probe
  bench_characterize        cold scheduler-fused characterize: wall-clock
                            + fused-wave-width telemetry (CI smoke records
                            this into benchmarks.smoke.json)
  bench_wave_fusion         per-instruction (legacy) vs scheduler-fused
                            characterization across SIM_UARCHES
  bench_corpus_eval         corpus-evaluation throughput: seeded block
                            corpus through fused mega-waves at several
                            wave widths, numpy vs jax wave backend, cold
                            vs warm lowering/jit caches
  bench_hardware_corpus     §6.2-analogue — real-JAX op corpus wall-clock
  bench_kernel_contention   blocking-kernel unit attribution harness
  table_roofline            §Roofline — dry-run roofline summary (if runs
                            exist under experiments/dryrun)
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

ROWS: list[tuple[str, float, str]] = []


def emit(name: str, us: float, derived: str = ""):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.2f},{derived}")


def _timed(f):
    t0 = time.perf_counter()
    out = f()
    return out, (time.perf_counter() - t0) * 1e6


# ---------------------------------------------------------------------------


def table1_characterization():
    """Table 1 analogue: one Campaign characterizes the full μISA on all
    simulated uarches concurrently; compare against the legacy (IACA-like,
    bug-planted) analyzer."""
    from repro.core.engine import Campaign
    from repro.core.isa import TEST_ISA
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_UARCHES

    # planted stale tables, mimicking documented IACA bug classes (§7.2)
    legacy_bugs = {
        "MOVQ2DQ_X_X": {frozenset("5"): 2},            # wrong ports (llvm/IACA)
        "IMUL_R64_M64": {frozenset("1"): 1},           # missing load μop
        "BSWAP_R32": {frozenset("15"): 2},             # variant confusion
        "SAHF": {frozenset("0156"): 1},                # extra ports (IACA>=2.2)
    }
    machines = [SimMachine(ua, TEST_ISA) for ua in SIM_UARCHES.values()]
    res = Campaign().run(machines, TEST_ISA)
    print("\n== Table 1: characterized variants & legacy agreement ==")
    print(f"{'uarch':10s} {'#instr':>6s} {'runtime_s':>9s} "
          f"{'uops_agree%':>11s} {'ports_agree%':>12s} {'cache_hit%':>10s}")
    for name, model in res.models.items():
        n = len(model.instructions)
        uops_ok = ports_ok = total = 0
        for iname, im in model.instructions.items():
            legacy_usage = legacy_bugs.get(iname, im.port_usage.usage)
            legacy_uops = sum(legacy_usage.values())
            total += 1
            uops_ok += int(round(im.uops) == legacy_uops)
            ports_ok += int(im.port_usage.usage == legacy_usage)
        print(f"{name:10s} {n:6d} {res.uarch_seconds[name]:9.1f} "
              f"{100 * uops_ok / total:11.2f} {100 * ports_ok / total:12.2f} "
              f"{100 * res.stats[name]['hit_rate']:10.1f}")
        emit(f"table1_{name}", res.uarch_seconds[name] * 1e6, f"instr={n}")
    phases = {k: round(v, 1) for k, v in
              sorted(res.phase_seconds[machines[0].name].items())}
    print(f"(campaign wall {res.wall_seconds:.1f}s across "
          f"{len(machines)} uarches; phase seconds: {phases})")
    emit("table1_campaign", res.wall_seconds * 1e6,
         f"hit_rate={res.hit_rate:.3f}")


def table_legacy_versions():
    """§7.2 'Differences Between Different IACA Versions': two legacy-table
    versions disagree on the same instruction; sometimes the newer one is
    right (MOVQ2DQ fixed), sometimes the older one is (SAHF regressed in
    v2, as IACA >= 2.2 did on Haswell). Measurement adjudicates."""
    from repro.core.blocking import find_blocking_instructions
    from repro.core.isa import TEST_ISA
    from repro.core.port_usage import infer_port_usage
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_SKL

    legacy_v1 = {  # old version: MOVQ2DQ wrong, SAHF right
        "MOVQ2DQ_X_X": {frozenset("5"): 2},
        "SAHF": {frozenset("06"): 1},
    }
    legacy_v2 = {  # new version: MOVQ2DQ fixed, SAHF regressed
        "MOVQ2DQ_X_X": {frozenset("0"): 1, frozenset("015"): 1},
        "SAHF": {frozenset("0156"): 1},
    }
    m = SimMachine(SIM_SKL, TEST_ISA)

    def work():
        blk = find_blocking_instructions(m, TEST_ISA)
        out = {}
        for n in ("MOVQ2DQ_X_X", "SAHF"):
            out[n] = infer_port_usage(m, TEST_ISA, n, blk, 4).usage
        return out

    measured, us = _timed(work)
    print("\n== §7.2: legacy-analyzer version differences, adjudicated ==")
    print(f"{'instr':14s} {'v1':>14s} {'v2':>14s} {'measured':>16s} {'right':>6s}")

    def fmt(u):
        return "+".join(f"{c}*p{''.join(sorted(pc))}"
                        for pc, c in sorted(u.items(), key=lambda kv: sorted(kv[0])))

    for n in ("MOVQ2DQ_X_X", "SAHF"):
        right = ("v2" if legacy_v2[n] == measured[n] else
                 "v1" if legacy_v1[n] == measured[n] else "none")
        print(f"{n:14s} {fmt(legacy_v1[n]):>14s} {fmt(legacy_v2[n]):>14s} "
              f"{fmt(measured[n]):>16s} {right:>6s}")
    emit("table_legacy_versions", us)


def table_throughput_defs():
    """§4.2: Intel-definition (LP from ports) vs Fog-definition (measured)."""
    from repro.core.characterize import characterize
    from repro.core.isa import TEST_ISA
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_SKL

    names = ["ADD_R64_R64", "CMC", "ADC_R64_R64", "SHL_R64_I8", "PADDD_X_X",
             "MULPS_X_X", "DIV_R64"]
    m = SimMachine(SIM_SKL, TEST_ISA)
    model, us = _timed(lambda: characterize(m, TEST_ISA, names))
    print("\n== §4.2: throughput definitions ==")
    print(f"{'instr':16s} {'tp_measured':>11s} {'tp_LP':>8s} {'diverge':>8s}")
    for n in names:
        tp = model[n].throughput
        lp = tp.computed_from_ports
        div = "yes" if lp is not None and abs(tp.measured - lp) > 0.1 else ""
        print(f"{n:16s} {tp.measured:11.2f} "
              f"{lp if lp is not None else float('nan'):8.2f} {div:>8s}")
    emit("table_throughput_defs", us)


def _lat_table(uarch_name, instr):
    from repro.core.isa import TEST_ISA
    from repro.core.latency import LatencyAnalyzer
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_UARCHES

    m = SimMachine(SIM_UARCHES[uarch_name], TEST_ISA)
    r, us = _timed(lambda: LatencyAnalyzer(m, TEST_ISA).analyze(instr))
    return r, us


def fig_case_aesdec():
    print("\n== §7.3.1: AESDEC per-pair latency across microarchitectures ==")
    print(f"{'uarch':10s} {'lat(op1->op1)':>14s} {'lat(op2->op1)':>14s}")
    tot = 0.0
    for ua in ("sim_snb", "sim_hsw", "sim_skl"):
        r, us = _lat_table(ua, "AESDEC_X_X")
        tot += us
        print(f"{ua:10s} {r.get('op1', 'op1').value:14.2f} "
              f"{r.get('op2', 'op1').value:14.2f}")
    print("(single-scalar tools report only the max; the 1-cycle round-key"
          " path on sim_snb is invisible to them)")
    emit("fig_case_aesdec", tot)


def fig_case_shld():
    print("\n== §7.3.2: SHLD same-register effect ==")
    print(f"{'uarch':10s} {'lat(op1,op1)':>12s} {'lat(op2,op1)':>12s} "
          f"{'same_reg':>9s}")
    tot = 0.0
    for ua in ("sim_snb", "sim_skl"):
        r, us = _lat_table(ua, "SHLD_R64_R64_I8")
        tot += us
        e = r.get("op2", "op1")
        print(f"{ua:10s} {r.get('op1', 'op1').value:12.2f} {e.value:12.2f} "
              f"{e.same_reg:9.2f}")
    print("(explains Fog=3 vs manual=4 on NHM-like, and Granlund/AIDA64=1 "
          "vs Fog=3 on SKL-like: different operand scenarios)")
    emit("fig_case_shld", tot)


def fig_case_movq2dq():
    from repro.core.blocking import find_blocking_instructions
    from repro.core.isa import TEST_ISA
    from repro.core.machine import isolation_ports
    from repro.core.port_usage import infer_port_usage
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_SKL

    m = SimMachine(SIM_SKL, TEST_ISA)

    def work():
        iso = isolation_ports(m, TEST_ISA["MOVQ2DQ_X_X"])
        blk = find_blocking_instructions(m, TEST_ISA)
        pu = infer_port_usage(m, TEST_ISA, "MOVQ2DQ_X_X", blk, 4)
        return iso, pu

    (iso, pu), us = _timed(work)
    print("\n== §7.3.3: MOVQ2DQ isolation fallacy ==")
    print("isolation per-port counts:",
          {p: round(v, 2) for p, v in sorted(iso.items())})
    print("naive conclusion: 1*p0+1*p15   (wrong)")
    print(f"Algorithm 1:      {pu.notation()}   (matches hidden truth)")
    emit("fig_case_movq2dq", us, pu.notation())


def table_multi_latency():
    from repro.core.isa import TEST_ISA
    from repro.core.latency import LatencyAnalyzer
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_SKL

    m = SimMachine(SIM_SKL, TEST_ISA)
    la = LatencyAnalyzer(m, TEST_ISA)
    names = ["MUL_R64", "ADC_R64_R64", "SHLD_R64_R64_I8", "ADD_R64_M64",
             "IMUL_R64_M64", "AESDEC_X_M", "BSWAP_R64", "MOVQ2DQ_X_X"]

    def work():
        out = []
        for n in names:
            r = la.analyze(n)
            vals = {e.value for e in r.entries.values() if e.kind == "exact"}
            if len(vals) > 1:
                out.append((n, sorted(vals)))
        return out

    rows, us = _timed(work)
    print("\n== §7.3.5: instructions with pair-dependent latencies ==")
    for n, vals in rows:
        print(f"  {n:18s} distinct latencies: {vals}")
    emit("table_multi_latency", us, f"found={len(rows)}")


def table_zero_idioms():
    from repro.core.isa import TEST_ISA
    from repro.core.latency import LatencyAnalyzer
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_SKL

    m = SimMachine(SIM_SKL, TEST_ISA)
    la = LatencyAnalyzer(m, TEST_ISA)
    cands = ["XOR_R64_R64", "SUBZ_R64_R64", "PCMPGTQ_X_X", "ADD_R64_R64",
             "PADDD_X_X"]

    def work():
        found = []
        for n in cands:
            r = la.analyze(n)
            e = r.get("op2", "op1")
            if e is not None and e.same_reg is not None and e.same_reg < 0.5:
                found.append(n)
        return found

    found, us = _timed(work)
    print("\n== §7.3.6: dependency-breaking idioms detected ==")
    print("  ", found, " (PCMPGTQ-family undocumented in the manual)")
    emit("table_zero_idioms", us, ";".join(found))


def bench_lp():
    import random

    from repro.core.lp import throughput_lp

    rng = random.Random(0)
    ports = "01234567"
    cases = []
    for _ in range(200):
        n = rng.randint(1, 5)
        cases.append({frozenset(rng.sample(ports, rng.randint(1, 4))):
                      rng.randint(1, 6) for _ in range(n)})

    def work():
        return [throughput_lp(c) for c in cases]

    _, us = _timed(work)
    print(f"\n== LP solver: {len(cases)} solves in {us / 1e3:.1f} ms ==")
    emit("bench_lp", us / len(cases), f"solves={len(cases)}")


def bench_simulator():
    from repro.core.isa import TEST_ISA
    from repro.core.machine import RegPool, independent_seq
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_SKL

    m = SimMachine(SIM_SKL, TEST_ISA)
    seq = independent_seq(TEST_ISA["ADD_R64_R64"], RegPool(), 16) * 200

    def work():
        return m.run(seq)

    c, us = _timed(work)
    rate = c.total_uops / (us / 1e6)
    print(f"\n== simulator: {rate / 1e6:.2f} Mμops/s ==")
    emit("bench_simulator", us, f"uops_per_s={rate:.0f}")


def bench_hardware_corpus():
    from repro.core.hardware import characterize_corpus
    from repro.ops.corpus import build_corpus

    corpus = build_corpus(sizes=(128, 256))

    def work():
        return characterize_corpus(corpus)

    res, us = _timed(work)
    print("\n== §6.2-analogue: real-JAX op corpus (this backend) ==")
    print(f"{'op':22s} {'lat_us':>8s} {'tput_us':>8s} {'GFLOP/s':>8s}")
    for name, r in res.items():
        print(f"{name:22s} {r.latency_ns / 1e3:8.2f} "
              f"{r.throughput_ns / 1e3:8.2f} {r.achieved_gflops:8.2f}")
        emit(f"hw_{name}", r.throughput_ns / 1e3,
             f"gflops={r.achieved_gflops:.2f}")
    emit("bench_hardware_corpus", us, f"ops={len(res)}")


def bench_kernel_contention():
    import jax.numpy as jnp

    from repro.core.kernel_bench import profile_kernel
    from repro.kernels import ref

    q = jnp.ones((1, 2, 128, 32), jnp.float32) * 0.1

    def target():
        return ref.reference_attention(q, q, q, causal=True)

    # CPU stand-ins for the blockers (kernels/microbench.py's run on TPU)
    a = jnp.ones((128, 128), jnp.float32)
    v = jnp.ones((1 << 14,), jnp.float32)
    blockers = {
        "MXU": lambda: (a @ a) * 1e-3,
        "VPU": lambda: v * 1.0001 + 0.5,
    }

    def work():
        return profile_kernel("attention", target, blockers)

    prof, us = _timed(work)
    print("\n== kernel contention harness (CPU: everything serializes) ==")
    print(f"  alone={prof.alone_ns / 1e3:.1f}us overlap="
          f"{ {k: round(v, 2) for k, v in prof.overlap.items()} }")
    emit("bench_kernel_contention", us)


BATCH_SIM_STATS: dict = {}


def bench_batch_sim(smoke: bool = False):
    """Wave execution: scalar per-experiment loop vs the batched array
    backends, over a wave-size sweep. Each wave item is one Algorithm-2
    experiment (body * n_small plus body * n_large), exactly what
    ``MeasurementEngine.submit`` hands to ``run_batch``. Results are
    checked bit-identical while being timed."""
    import random
    import time as _time

    from repro.core.batch_sim import BatchSimMachine
    from repro.core.isa import TEST_ISA
    from repro.core.machine import RegPool, independent_seq
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_SKL

    try:
        import jax  # noqa: F401
        have_jax = True
    except ImportError:
        have_jax = False

    specs = ["ADD_R64_R64", "IMUL_R64_R64", "MOV_R64_R64",
             "SHLD_R64_R64_I8", "PADDD_X_X", "MOV_R64_M64", "ADC_R64_R64",
             "MULPS_X_X", "DIV_R64", "AESDEC_X_X"]
    scalar = SimMachine(SIM_SKL, TEST_ISA)
    sweep = (8,) if smoke else (32, 128, 256)
    rows = []
    print("\n== vectorized measurement substrate: wave-size sweep ==")
    print(f"{'wave':>6s} {'scalar_s':>9s} {'numpy_s':>8s} {'np_x':>6s} "
          f"{'jax_s':>8s} {'jax_x':>6s}")
    for wave in sweep:
        rng = random.Random(wave)
        codes = []
        for _ in range(wave):
            body = independent_seq(TEST_ISA[rng.choice(specs)], RegPool(),
                                   rng.randint(4, 12))
            codes.append(body * 10)
            codes.append(body * 110)
        t0 = _time.perf_counter()
        ref = [scalar.run(list(c)) for c in codes]
        t_scalar = _time.perf_counter() - t0

        def timed_backend(backend):
            m = BatchSimMachine(SIM_SKL, TEST_ISA, backend=backend)
            m.run_batch(codes)   # warm: recipe caches + jit shape buckets
            t0 = _time.perf_counter()
            got = m.run_batch(codes)
            dt = _time.perf_counter() - t0
            assert all(r.cycles == g.cycles and r.port_uops == g.port_uops
                       for r, g in zip(ref, got)), \
                f"{backend} backend diverged from the scalar oracle"
            return dt

        t_np = timed_backend("numpy")
        t_jax = timed_backend("jax") if have_jax else None
        np_x = t_scalar / t_np
        jax_x = (t_scalar / t_jax) if t_jax else None
        print(f"{wave:6d} {t_scalar:9.3f} {t_np:8.3f} {np_x:5.1f}x "
              f"{t_jax if t_jax is not None else float('nan'):8.3f} "
              f"{f'{jax_x:.1f}x' if jax_x else '---':>6s}")
        emit(f"batch_sim_w{wave}_numpy", t_np * 1e6 / (2 * wave),
             f"speedup={np_x:.1f}x")
        if t_jax is not None:
            emit(f"batch_sim_w{wave}_jax", t_jax * 1e6 / (2 * wave),
                 f"speedup={jax_x:.1f}x")
        rows.append({"wave": wave, "scalar_s": round(t_scalar, 4),
                     "numpy_s": round(t_np, 4),
                     "numpy_speedup": round(np_x, 2),
                     "jax_s": round(t_jax, 4) if t_jax else None,
                     "jax_speedup": round(jax_x, 2) if jax_x else None})
    best = max(r["numpy_speedup"] for r in rows)
    target_rows = [r for r in rows if r["wave"] >= 256]
    meets = all(r["numpy_speedup"] >= 5 for r in target_rows) \
        if target_rows else None
    if meets is not None:
        print(f"  wave>=256 numpy speedup "
              f"{'meets' if meets else 'MISSES'} the >=5x target")

    # thin-chunk crossover: smallest lane count where the batched kernel
    # beats the scalar oracle loop — the measured basis for the
    # SimMachine/BatchSimMachine ``min_lanes`` default
    from repro.core.batch_sim import DEFAULT_MIN_LANES
    cross_rows = []
    crossover = None
    widths = (2, 4) if smoke else (2, 4, 6, 8, 12, 16, 24)
    for lanes in widths:
        rng = random.Random(1000 + lanes)
        thin = [independent_seq(TEST_ISA[rng.choice(specs)], RegPool(),
                                rng.randint(4, 12)) * 10
                for _ in range(lanes)]
        t0 = _time.perf_counter()
        for _ in range(5):
            for c in thin:
                scalar.run(list(c))
        t_sc = (_time.perf_counter() - t0) / 5
        mb = BatchSimMachine(SIM_SKL, TEST_ISA, min_lanes=1)
        mb.run_batch(thin)
        t0 = _time.perf_counter()
        for _ in range(5):
            mb.run_batch(thin)
        t_b = (_time.perf_counter() - t0) / 5
        cross_rows.append({"lanes": lanes, "scalar_s": round(t_sc, 5),
                           "batched_s": round(t_b, 5)})
        if crossover is None and t_b < t_sc:
            crossover = lanes
    print(f"  thin-chunk crossover: batched kernel wins from "
          f"{crossover} lanes (min_lanes default {DEFAULT_MIN_LANES})")
    BATCH_SIM_STATS.update({"sweep": rows, "best_numpy_speedup": best,
                            "meets_5x_target_at_256": meets,
                            "jax_available": have_jax,
                            "min_lanes_sweep": cross_rows,
                            "min_lanes_crossover": crossover,
                            "min_lanes_default": DEFAULT_MIN_LANES})


BACKEND_MATRIX_STATS: dict = {}


def bench_backend_matrix(smoke: bool = False):
    """Device-resident wave execution: numpy vs jax (blocked AOT scan)
    across wave widths, with a cold and a warm lowering-cache pass per
    cell.  Kernel compilation is shared
    module-wide per shape bucket, so the cold pass measures lowering +
    packing + execution (one pre-pass per backend absorbs compiles and
    feeds the recompile probe: a fresh machine over the same shapes must
    trigger zero new compilations).  Results are asserted bit-identical to
    the scalar ``SimMachine`` oracle while being timed."""
    import random
    import time as _time

    from repro.core.batch_sim import BatchSimMachine
    from repro.core.isa import TEST_ISA
    from repro.core.machine import RegPool, independent_seq
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_SKL

    try:
        import jax  # noqa: F401
        backends = ("numpy", "jax")
    except ImportError:
        backends = ("numpy",)

    specs = ["ADD_R64_R64", "IMUL_R64_R64", "MOV_R64_R64",
             "SHLD_R64_R64_I8", "PADDD_X_X", "MOV_R64_M64", "ADC_R64_R64",
             "MULPS_X_X", "DIV_R64", "AESDEC_X_X"]
    scalar = SimMachine(SIM_SKL, TEST_ISA)
    waves = (8, 32) if smoke else (32, 128, 512)
    rows = []
    print("\n== backend matrix: numpy / jax scan, cold+warm lowering "
          "cache ==")
    print(f"{'wave':>6s} {'backend':>8s} {'cold_s':>8s} {'warm_s':>8s} "
          f"{'vs_numpy':>9s} {'compiles':>9s}")
    for wave in waves:
        rng = random.Random(wave)
        codes = []
        for _ in range(wave):
            body = independent_seq(TEST_ISA[rng.choice(specs)], RegPool(),
                                   rng.randint(4, 12))
            codes.append(body * 10)
            codes.append(body * 110)
        ref = [scalar.run(list(c)) for c in codes]
        numpy_warm = None
        for backend in backends:
            # pre-pass on a throwaway machine: absorbs kernel compilation
            # (module-wide per bucket) so cold isolates the lowering cache
            pre = BatchSimMachine(SIM_SKL, TEST_ISA, backend=backend)
            pre.run_batch(codes)
            pre_compiles = pre.device_stats().get("compiles", 0)
            m = BatchSimMachine(SIM_SKL, TEST_ISA, backend=backend)
            t0 = _time.perf_counter()
            got = m.run_batch(codes)
            cold = _time.perf_counter() - t0
            assert all(r.cycles == g.cycles and r.port_uops == g.port_uops
                       for r, g in zip(ref, got)), \
                f"{backend} backend diverged from the scalar oracle"
            warm = min(_timed(lambda: m.run_batch(codes))[1]
                       for _ in range(3)) / 1e6
            dstats = m.device_stats()
            recompiles = dstats.get("compiles", 0)
            buckets = len(dstats.get("buckets", ()))
            # recompile probe: the pre-pass compiled every bucket, so the
            # measured machine must not have triggered a single compile
            assert recompiles == 0, \
                f"{backend}: {recompiles} recompiles for already-" \
                f"compiled buckets (bucketing regressed)"
            assert pre_compiles <= max(buckets, 1), \
                f"{backend}: {pre_compiles} compiles for {buckets} " \
                f"shape buckets (more than one compile per bucket)"
            if backend == "numpy":
                numpy_warm = warm
            speed = numpy_warm / warm if numpy_warm else float("nan")
            print(f"{wave:6d} {backend:>8s} {cold:8.3f} {warm:8.4f} "
                  f"{speed:8.2f}x {pre_compiles:9d}")
            emit(f"backend_matrix_w{wave}_{backend}",
                 warm * 1e6 / (2 * wave), f"vs_numpy={speed:.2f}x")
            rows.append({"wave": wave, "backend": backend,
                         "cold_s": round(cold, 4),
                         "warm_s": round(warm, 4),
                         "warm_speedup_vs_numpy": round(speed, 2),
                         "compiles": pre_compiles, "buckets": buckets,
                         "lowering": dict(m.lowering_stats)})
    target = [r for r in rows if r["backend"] == "jax" and r["wave"] >= 128]
    meets = all(r["warm_speedup_vs_numpy"] >= 2 for r in target) \
        if target else None
    if meets is not None:
        print(f"  jax backend at wave>=128 "
              f"{'meets' if meets else 'MISSES'} the >=2x-vs-numpy target")
    BACKEND_MATRIX_STATS.update({
        "matrix": rows, "backends": list(backends),
        "meets_2x_target_at_128": meets})


TRACE_OVERHEAD_STATS: dict = {}


def bench_trace_overhead(smoke: bool = False):
    """Observability tax: the backend-matrix wave sweep on the numpy
    backend with tracing disabled vs enabled (repro.obs).  Two numbers
    matter:

    * the measured enabled/disabled wall ratio (spans are per-wave, not
      per-μop, so it should be within noise of 1.0);
    * the **analytic disabled-overhead bound** — spans-per-pass × the
      measured cost of one disabled span call, as a share of the
      disabled wall time.  This is the number the <2% gate asserts: it
      is deterministic, unlike the A/B ratio, which on a busy CI host
      can swing either way by more than the effect being measured.
    """
    import random
    import time as _time

    from repro.core.batch_sim import BatchSimMachine
    from repro.core.isa import TEST_ISA
    from repro.core.machine import RegPool, independent_seq
    from repro.core.uarch import SIM_SKL
    from repro.obs import tracer as obs
    from repro.obs.tracer import Tracer, set_tracer

    specs = ["ADD_R64_R64", "IMUL_R64_R64", "MOV_R64_R64",
             "SHLD_R64_R64_I8", "PADDD_X_X", "MOV_R64_M64", "ADC_R64_R64",
             "MULPS_X_X", "DIV_R64", "AESDEC_X_X"]
    wave = 32 if smoke else 128
    rng = random.Random(wave)   # same wave construction as backend matrix
    codes = []
    for _ in range(wave):
        body = independent_seq(TEST_ISA[rng.choice(specs)], RegPool(),
                               rng.randint(4, 12))
        codes.append(body * 10)
        codes.append(body * 110)
    m = BatchSimMachine(SIM_SKL, TEST_ISA, backend="numpy")
    m.run_batch(codes)          # absorb compiles + cold lowering

    reps = 3 if smoke else 5
    prev = set_tracer(Tracer(enabled=False))
    try:
        t_off = min(_timed(lambda: m.run_batch(codes))[1]
                    for _ in range(reps)) / 1e6
        tr = Tracer(enabled=True)
        set_tracer(tr)
        t_on = min(_timed(lambda: m.run_batch(codes))[1]
                   for _ in range(reps)) / 1e6
        spans_per_pass = len(tr.events()) / reps

        # cost of one disabled span call, measured on the real no-op path
        set_tracer(Tracer(enabled=False))
        n = 100_000
        t0 = _time.perf_counter_ns()
        for _ in range(n):
            with obs.span("bench.noop", probe=1):
                pass
        noop_ns = (_time.perf_counter_ns() - t0) / n
    finally:
        set_tracer(prev)

    ratio = t_on / t_off
    bound = spans_per_pass * noop_ns / (t_off * 1e9)
    print("\n== tracing overhead: numpy wave sweep, repro.obs on vs off ==")
    print(f"{'wave':>6s} {'off_s':>8s} {'on_s':>8s} {'on/off':>7s} "
          f"{'spans':>7s} {'noop_ns':>8s} {'bound%':>7s}")
    print(f"{wave:6d} {t_off:8.4f} {t_on:8.4f} {ratio:6.3f}x "
          f"{spans_per_pass:7.0f} {noop_ns:8.1f} {100 * bound:6.4f}%")
    assert bound < 0.02, \
        f"disabled-tracing overhead bound {100 * bound:.3f}% >= 2% " \
        f"({spans_per_pass:.0f} spans/pass x {noop_ns:.0f}ns noop over " \
        f"{t_off:.4f}s)"
    emit("trace_overhead_off", t_off * 1e6 / (2 * wave),
         f"bound={100 * bound:.4f}%")
    emit("trace_overhead_on", t_on * 1e6 / (2 * wave),
         f"on/off={ratio:.3f}x")
    TRACE_OVERHEAD_STATS.update({
        "wave": wave, "t_off_s": round(t_off, 4), "t_on_s": round(t_on, 4),
        "enabled_over_disabled": round(ratio, 4),
        "spans_per_pass": spans_per_pass,
        "disabled_span_ns": round(noop_ns, 1),
        "disabled_overhead_bound_pct": round(100 * bound, 4),
        "bound_ok": bound < 0.02})


FAULT_OVERHEAD_STATS: dict = {}


def bench_fault_overhead(smoke: bool = False):
    """Chaos tax: the numpy wave sweep with no fault plan installed vs an
    *armed-but-never-firing* plan (p=0 rules at every injection point).
    Same discipline as ``bench_trace_overhead``: the asserted <2% gate is
    the **analytic disabled-path bound** — injection checks per pass × the
    measured cost of one disabled ``faults.check`` call, as a share of the
    plan-free wall time — because the A/B ratio is noise-dominated on a
    busy host."""
    import random
    import time as _time

    from repro.core.batch_sim import BatchSimMachine
    from repro.core.isa import TEST_ISA
    from repro.core.machine import RegPool, independent_seq
    from repro.core.uarch import SIM_SKL
    from repro.faults import plan as faults
    from repro.faults.plan import POINTS, FaultPlan

    specs = ["ADD_R64_R64", "IMUL_R64_R64", "MOV_R64_R64",
             "SHLD_R64_R64_I8", "PADDD_X_X", "MOV_R64_M64", "ADC_R64_R64",
             "MULPS_X_X", "DIV_R64", "AESDEC_X_X"]
    wave = 32 if smoke else 128
    rng = random.Random(wave)   # same wave construction as backend matrix
    codes = []
    for _ in range(wave):
        body = independent_seq(TEST_ISA[rng.choice(specs)], RegPool(),
                               rng.randint(4, 12))
        codes.append(body * 10)
        codes.append(body * 110)
    m = BatchSimMachine(SIM_SKL, TEST_ISA, backend="numpy")
    m.run_batch(codes)          # absorb compiles + cold lowering

    reps = 3 if smoke else 5
    prev = faults.set_plan(None)
    try:
        t_off = min(_timed(lambda: m.run_batch(codes))[1]
                    for _ in range(reps)) / 1e6
        armed = FaultPlan.from_spec(
            ";".join(f"{p}:raise:p=0" for p in POINTS))
        faults.set_plan(armed)
        t_on = min(_timed(lambda: m.run_batch(codes))[1]
                   for _ in range(reps)) / 1e6
        checks_per_pass = armed.occurrences() / reps
        assert not armed.fired

        # cost of one disabled check, measured on the real fast path
        faults.set_plan(None)
        n = 100_000
        t0 = _time.perf_counter_ns()
        for _ in range(n):
            faults.check("wave.kernel", key="bench")
        noop_ns = (_time.perf_counter_ns() - t0) / n
    finally:
        faults.set_plan(prev)

    ratio = t_on / t_off
    bound = checks_per_pass * noop_ns / (t_off * 1e9)
    print("\n== fault-injection overhead: numpy wave sweep, plan off vs "
          "armed p=0 ==")
    print(f"{'wave':>6s} {'off_s':>8s} {'on_s':>8s} {'on/off':>7s} "
          f"{'checks':>7s} {'noop_ns':>8s} {'bound%':>7s}")
    print(f"{wave:6d} {t_off:8.4f} {t_on:8.4f} {ratio:6.3f}x "
          f"{checks_per_pass:7.0f} {noop_ns:8.1f} {100 * bound:6.4f}%")
    assert bound < 0.02, \
        f"disabled-injection overhead bound {100 * bound:.3f}% >= 2% " \
        f"({checks_per_pass:.0f} checks/pass x {noop_ns:.0f}ns noop over " \
        f"{t_off:.4f}s)"
    emit("fault_overhead_off", t_off * 1e6 / (2 * wave),
         f"bound={100 * bound:.4f}%")
    emit("fault_overhead_armed", t_on * 1e6 / (2 * wave),
         f"armed/off={ratio:.3f}x")
    FAULT_OVERHEAD_STATS.update({
        "wave": wave, "t_off_s": round(t_off, 4), "t_on_s": round(t_on, 4),
        "armed_over_disabled": round(ratio, 4),
        "checks_per_pass": checks_per_pass,
        "disabled_check_ns": round(noop_ns, 1),
        "disabled_overhead_bound_pct": round(100 * bound, 4),
        "bound_ok": bound < 0.02})


DEVICE_SCALING_STATS: dict = {}

# worker for bench_device_scaling: runs in a subprocess because
# XLA_FLAGS=--xla_force_host_platform_device_count must be set before jax
# is first imported, and the parent process has usually imported it
# already.  CPU hosts only: on an accelerator the parent holds the chip, so
# a child cannot take it (and the forced host-device flag does not apply);
# `python chip_smoke.py --chips 4` drives the mesh on four real chips.
# Prints one JSON document on the last stdout line.
_DEVICE_SCALING_WORKER = """
import json, os, random, time
from repro.core.batch_sim import BatchSimMachine
from repro.core.isa import TEST_ISA
from repro.core.machine import RegPool, independent_seq
from repro.core.uarch import SIM_SKL
import jax

smoke = os.environ.get("BENCH_SMOKE") == "1"
waves = (8, 32) if smoke else (32, 128, 512)
specs = ["ADD_R64_R64", "IMUL_R64_R64", "MOV_R64_R64", "SHLD_R64_R64_I8",
         "PADDD_X_X", "MOV_R64_M64", "ADC_R64_R64", "MULPS_X_X", "DIV_R64",
         "AESDEC_X_X"]
rows = []
for wave in waves:
    rng = random.Random(wave)   # same wave construction as backend matrix
    codes = []
    for _ in range(wave):
        body = independent_seq(TEST_ISA[rng.choice(specs)], RegPool(),
                               rng.randint(4, 12))
        codes.append(body * 10)
        codes.append(body * 110)
    ref = BatchSimMachine(SIM_SKL, TEST_ISA, backend="numpy").run_batch(codes)
    for nd in (1, 2, 4):
        m = BatchSimMachine(SIM_SKL, TEST_ISA, backend="jax", devices=nd)
        got = m.run_batch(codes)            # cold: compiles + lowering
        assert all(a.cycles == b.cycles and a.port_uops == b.port_uops
                   for a, b in zip(ref, got)), ("bit-identity", wave, nd)
        warm = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            m.run_batch(codes)
            warm = min(warm, time.perf_counter() - t0)
        st = m.device_stats()
        c0 = st["compiles"]
        m.run_batch(codes)                  # recompile probe
        c1 = m.device_stats()["compiles"]
        if c1 != c0:
            raise AssertionError(
                f"unexpected recompiles at wave={wave} devices={nd}: "
                f"{c1 - c0} new compiles on a warm wave")
        rows.append({"wave": wave, "devices": nd,
                     "warm_s": round(warm, 4),
                     "exps_per_s": round(2 * wave / warm, 1),
                     "compiles": c0, "mesh": st["mesh"],
                     "per_device_lanes": {
                         k: v["lanes"]
                         for k, v in st["per_device"].items()}})
print(json.dumps({"rows": rows, "cpu_count": os.cpu_count(),
                  "jax_devices": len(jax.devices())}))
"""


def bench_device_scaling(smoke: bool = False):
    """Mesh-parallel wave execution: warm wave throughput at 1, 2 and 4
    forced host devices on the backend-matrix wave widths, asserted
    bit-identical to the numpy backend and failing on any warm-wave
    recompile.  Runs in a subprocess so the forced host-device count can
    be injected before jax's first import.  NOTE: forced host devices
    share the machine's physical cores — wall-clock scaling tracks the
    spare core count (``cpu_count`` is recorded alongside), and on a
    single-core host the 4-device row measures sharding overhead, not
    speedup; real accelerators (or real cores) are where the mesh pays.
    CPU-only: on an accelerator it raises (see ``chip_smoke.py --chips
    4`` for the four-chip mesh)."""
    import json as _json
    import os
    import subprocess

    try:
        import jax
    except ImportError:
        print("\n== device scaling: jax unavailable, skipped ==")
        DEVICE_SCALING_STATS.update({"skipped": "jax not importable"})
        return
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"bench_device_scaling forces host devices in a child process, "
            f"which cannot take the {jax.default_backend()} chip this "
            f"process holds; run `python chip_smoke.py --chips 4` for the "
            f"multi-chip mesh instead")
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=4").strip()
    env["BENCH_SMOKE"] = "1" if smoke else "0"
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _DEVICE_SCALING_WORKER],
                          env=env, capture_output=True, text=True,
                          timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError("bench_device_scaling worker failed:\n"
                           + proc.stderr[-3000:])
    data = _json.loads(proc.stdout.strip().splitlines()[-1])
    rows = data["rows"]
    print(f"\n== device scaling: warm wave throughput vs forced host "
          f"devices (host cpu_count={data['cpu_count']}) ==")
    print(f"{'wave':>6s} {'devices':>8s} {'warm_s':>8s} {'exps/s':>10s} "
          f"{'vs_1dev':>8s}")
    speedups = {}
    for r in rows:
        base = next(b["warm_s"] for b in rows
                    if b["wave"] == r["wave"] and b["devices"] == 1)
        speed = base / r["warm_s"]
        if r["devices"] == 4:
            speedups[r["wave"]] = round(speed, 2)
        print(f"{r['wave']:6d} {r['devices']:8d} {r['warm_s']:8.4f} "
              f"{r['exps_per_s']:10.1f} {speed:7.2f}x")
        emit(f"device_scaling_w{r['wave']}_d{r['devices']}",
             r["warm_s"] * 1e6 / (2 * r["wave"]), f"vs_1dev={speed:.2f}x")
    best = max(speedups.values(), default=float("nan"))
    meets = best >= 1.6
    print(f"  4-device speedup {best:.2f}x "
          f"{'meets' if meets else 'MISSES'} the >=1.6x target "
          f"(host has {data['cpu_count']} cpu core(s); forced host "
          f"devices can only scale across spare cores)")
    DEVICE_SCALING_STATS.update({
        "rows": rows, "speedup_4v1_by_wave": speedups,
        "best_speedup_4v1": best, "meets_1p6x_target": meets,
        "cpu_count": data["cpu_count"],
        "jax_devices": data["jax_devices"]})


CHARACTERIZE_STATS: dict = {}

# representative subset for the CI smoke artifact: big enough that wave
# fusion is visible, small enough to stay in CI budget
SMOKE_SUBSET = ["ADD_R64_R64", "ADC_R64_R64", "MOVQ2DQ_X_X", "MUL_R64",
                "SHLD_R64_R64_I8", "MOV_M64_R64", "DIV_R64", "AESDEC_X_X",
                "IMUL_R64_M64", "CMC", "PADDD_X_X", "PSHUFD_X_X"]


def bench_characterize(smoke: bool = False):
    """Cold scheduler-fused characterization: wall-clock and wave-width
    telemetry. The smoke variant (CI) characterizes a fixed instruction
    subset and records cold wall-clock + mean fused-wave width into
    experiments/benchmarks.smoke.json, so wave-fusion regressions show up
    in the artifact diff; the full variant runs the whole μISA."""
    import time as _time

    from repro.core.characterize import characterize
    from repro.core.engine import MeasurementEngine
    from repro.core.isa import TEST_ISA
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_SKL

    names = SMOKE_SUBSET if smoke else None
    m = SimMachine(SIM_SKL, TEST_ISA)
    t0 = _time.perf_counter()
    model = characterize(MeasurementEngine(m), TEST_ISA, names)
    cold_s = _time.perf_counter() - t0
    ws = model.wave_stats
    print(f"\n== cold characterize ({'smoke subset' if smoke else 'full'}"
          f" μISA, scheduler-fused) ==")
    print(f"  {len(model.instructions)} variants in {cold_s:.2f}s: "
          f"{ws['waves']} fused waves, mean width "
          f"{ws['mean_wave_width']:.1f}, max {ws['max_wave_width']}")
    emit("bench_characterize_cold", cold_s * 1e6,
         f"mean_wave_width={ws['mean_wave_width']};waves={ws['waves']}")
    # same cold characterization on the device-resident jax backend: the
    # wave-execution speedup as seen by a whole inference pipeline
    jax_cold = None
    try:
        import jax  # noqa: F401
    except ImportError:
        pass
    else:
        # first-ever pass pays one XLA compile per shape bucket (shared
        # module-wide afterwards); the second fresh machine is the
        # steady-state story — kernels compiled, lowering cache cold
        mj0 = SimMachine(SIM_SKL, TEST_ISA, backend="jax")
        t0 = _time.perf_counter()
        characterize(MeasurementEngine(mj0), TEST_ISA, names)
        jax_first = _time.perf_counter() - t0
        mj = SimMachine(SIM_SKL, TEST_ISA, backend="jax")
        t0 = _time.perf_counter()
        mdl = characterize(MeasurementEngine(mj), TEST_ISA, names)
        jax_cold = _time.perf_counter() - t0
        n_buckets = len(mj0.device_stats().get("buckets", ()))
        print(f"  jax backend: {jax_cold:.2f}s cold "
              f"({cold_s / jax_cold:.2f}x vs numpy; first-ever run "
              f"{jax_first:.2f}s incl. {n_buckets}-bucket compilation; "
              f"lowering {mj.lowering_stats})")
        emit("bench_characterize_cold_jax", jax_cold * 1e6,
             f"vs_numpy={cold_s / jax_cold:.2f}x")
        es = mdl.engine_stats
        CHARACTERIZE_STATS["jax_backend"] = {
            "cold_seconds": round(jax_cold, 3),
            "first_run_with_compiles_seconds": round(jax_first, 3),
            "speedup_vs_numpy": round(cold_s / jax_cold, 2),
            "lowering_hits": es["lowering_hits"],
            "lowering_misses": es["lowering_misses"],
            "device": mj.device_stats()}
    CHARACTERIZE_STATS.update({
        "smoke": smoke, "instructions": len(model.instructions),
        "cold_seconds": round(cold_s, 3),
        "mean_wave_width": ws["mean_wave_width"],
        "max_wave_width": ws["max_wave_width"], "waves": ws["waves"],
        "experiments": ws["experiments"],
        "engine_hit_rate": model.engine_stats["hit_rate"]})


WAVE_FUSION_STATS: dict = {}


def bench_wave_fusion():
    """Measurement-plan scheduler: per-instruction (legacy sequential
    driver) vs scheduler-fused characterization — wave widths and cold
    wall-clock across SIM_UARCHES. Model XML asserted identical while
    being timed, so the speedup is measured on byte-equivalent work."""
    import time as _time

    from repro.core import model_io
    from repro.core.characterize import characterize
    from repro.core.engine import MeasurementEngine
    from repro.core.isa import TEST_ISA
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_UARCHES

    rows = []
    print("\n== wave fusion: per-instruction (legacy) vs scheduler-fused ==")
    print(f"{'uarch':10s} {'seq_s':>7s} {'fused_s':>8s} {'speedup':>8s} "
          f"{'seq_w':>6s} {'fused_w':>8s} {'width_x':>8s}")
    for name, ua in SIM_UARCHES.items():
        t0 = _time.perf_counter()
        seq = characterize(MeasurementEngine(SimMachine(ua, TEST_ISA)),
                           TEST_ISA, sequential=True)
        t_seq = _time.perf_counter() - t0
        t0 = _time.perf_counter()
        fused = characterize(MeasurementEngine(SimMachine(ua, TEST_ISA)),
                             TEST_ISA)
        t_fus = _time.perf_counter() - t0
        assert model_io.to_xml(fused, TEST_ISA) == \
            model_io.to_xml(seq, TEST_ISA), \
            f"{name}: fused characterization diverged from sequential"
        w_seq = seq.wave_stats["mean_wave_width"]
        w_fus = fused.wave_stats["mean_wave_width"]
        speed, width_x = t_seq / t_fus, w_fus / w_seq
        print(f"{name:10s} {t_seq:7.2f} {t_fus:8.2f} {speed:7.1f}x "
              f"{w_seq:6.2f} {w_fus:8.1f} {width_x:7.1f}x")
        emit(f"wave_fusion_{name}", t_fus * 1e6,
             f"speedup={speed:.1f}x;width_x={width_x:.1f}x")
        rows.append({"uarch": name, "sequential_s": round(t_seq, 3),
                     "fused_s": round(t_fus, 3),
                     "speedup": round(speed, 2),
                     "sequential_mean_wave_width": w_seq,
                     "fused_mean_wave_width": w_fus,
                     "wave_width_ratio": round(width_x, 1),
                     "fused_max_wave_width":
                         fused.wave_stats["max_wave_width"]})
    mean_speed = sum(r["speedup"] for r in rows) / len(rows)
    mean_width = sum(r["wave_width_ratio"] for r in rows) / len(rows)
    meets_w = all(r["wave_width_ratio"] >= 10 for r in rows)
    meets_t = all(r["speedup"] >= 2 for r in rows)
    print(f"  mean: {mean_speed:.1f}x wall-clock, {mean_width:.0f}x wave "
          f"width ({'meets' if meets_w else 'MISSES'} the >=10x width "
          f"target, {'meets' if meets_t else 'MISSES'} the >=2x cold "
          f"wall-clock target)")
    WAVE_FUSION_STATS.update({
        "per_uarch": rows, "mean_speedup": round(mean_speed, 2),
        "mean_wave_width_ratio": round(mean_width, 1),
        "meets_10x_width_target": meets_w,
        "meets_2x_speedup_target": meets_t})


CAMPAIGN_STATS: dict = {}


def bench_campaign_cache():
    """Measurement-engine cache: cold vs warm campaign over all uarches.

    The warm pass re-runs the identical campaign against the same machines
    (whose engines now hold every result), standing in for an incremental
    ``characterize()`` re-run from a persisted cache."""
    import time as _time

    from repro.core.engine import Campaign
    from repro.core.isa import TEST_ISA
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_UARCHES

    machines = [SimMachine(ua, TEST_ISA) for ua in SIM_UARCHES.values()]
    camp = Campaign()
    t0 = _time.perf_counter()
    cold = camp.run(machines, TEST_ISA)
    cold_s = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    warm = camp.run(machines, TEST_ISA)
    warm_s = _time.perf_counter() - t0
    speedup = cold_s / max(warm_s, 1e-9)
    CAMPAIGN_STATS.update({
        "cold_seconds": round(cold_s, 3), "warm_seconds": round(warm_s, 3),
        "speedup_warm_vs_cold": round(speedup, 2),
        "cold_hit_rate": round(cold.hit_rate, 4),
        "warm_hit_rate": round(warm.hit_rate, 4),
        "per_uarch": {n: cold.stats[n] for n in cold.stats},
    })
    print("\n== measurement-engine cache: cold vs warm campaign ==")
    print(f"  cold {cold_s:.2f}s (hit rate {100 * cold.hit_rate:.1f}%)  "
          f"warm {warm_s:.2f}s (hit rate {100 * warm.hit_rate:.1f}%)  "
          f"speedup {speedup:.1f}x")
    emit("bench_campaign_cold", cold_s * 1e6,
         f"hit_rate={cold.hit_rate:.3f}")
    emit("bench_campaign_warm", warm_s * 1e6,
         f"speedup={speedup:.1f}x")


SERVICE_STATS: dict = {}


def bench_service_throughput():
    """uops-as-a-service: requests/sec over a batch-size sweep, cold vs
    warm cache, against the uncached single-block reference predictor.

    Two layers are measured: the *service* layer (registry + LRU cache +
    vectorized batch predictor, queried in-process — comparable to the
    baseline, which is also in-process) and the *wire* layer (full TCP +
    JSON round trip through the client). The >=50x warm-cache target is
    judged at the service layer; the wire numbers show the transport tax."""
    import tempfile
    import time as _time
    from pathlib import Path as _Path

    from repro.core import model_io
    from repro.core.engine import Campaign
    from repro.core.isa import TEST_ISA
    from repro.core.predictor import predict
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_SKL
    from repro.service.client import local_service
    from repro.service.registry import ModelRegistry
    from repro.service.server import PredictionService
    from repro.service.workload import random_blocks

    machine = SimMachine(SIM_SKL, TEST_ISA)
    names = ["ADD_R64_R64", "IMUL_R64_R64", "MUL_R64", "ADC_R64_R64", "CMC",
             "TEST_R64_R64", "SHLD_R64_R64_I8", "MOVQ2DQ_X_X", "AESDEC_X_X",
             "PSHUFD_X_X", "PADDD_X_X", "MOV_R64_M64"]
    res = Campaign(instr_names=names).run([machine], TEST_ISA)
    model = res.models[machine.name]
    tmpdir = tempfile.TemporaryDirectory(prefix="uops_service_bench_")
    tmp = _Path(tmpdir.name)
    (tmp / f"{machine.name}.xml").write_text(model_io.to_xml(model, TEST_ISA))
    ua = machine.name

    n_blocks = 256
    blocks = random_blocks(model, TEST_ISA, n_blocks, seed=17)

    # baseline: the uncached single-block reference path (min of 3 passes,
    # the noise-robust estimator; same estimator as the warm passes below)
    def baseline_pass():
        t0 = _time.perf_counter()
        for b in blocks:
            predict(model, TEST_ISA, b)
        return (_time.perf_counter() - t0) * 1e6 / n_blocks

    base_us = min(baseline_pass() for _ in range(3))
    emit("service_baseline_single", base_us, "reference predict()")

    def sweep_layer(layer, run_chunk, batch_sizes, make_ctx, close_ctx):
        rows = []
        print(f"{'layer':>8s} {'batch':>6s} {'cold_us/req':>12s} "
              f"{'warm_us/req':>12s} {'warm_rps':>9s} {'speedup':>8s}")
        for bs in batch_sizes:
            ctx = make_ctx()
            try:
                def run_pass():
                    t0 = _time.perf_counter()
                    for i in range(0, n_blocks, bs):
                        run_chunk(ctx, blocks[i:i + bs], bs)
                    return (_time.perf_counter() - t0) * 1e6 / n_blocks

                cold_us = run_pass()   # empty cache: every block computed
                # identical requests: pure cache hits (min of 3 passes)
                warm_us = min(run_pass() for _ in range(3))
            finally:
                close_ctx(ctx)
            speedup = base_us / warm_us
            print(f"{layer:>8s} {bs:6d} {cold_us:12.1f} {warm_us:12.1f} "
                  f"{1e6 / warm_us:9.0f} {speedup:7.1f}x")
            emit(f"service_{layer}_warm_b{bs}", warm_us,
                 f"rps={1e6 / warm_us:.0f};speedup={speedup:.1f}x")
            rows.append({"layer": layer, "batch": bs,
                         "cold_us_per_req": round(cold_us, 2),
                         "warm_us_per_req": round(warm_us, 2),
                         "warm_rps": round(1e6 / warm_us),
                         "warm_speedup_vs_single": round(speedup, 1)})
        return rows

    print("\n== uops-as-a-service throughput (batch-size sweep) ==")
    print(f"  baseline (uncached single-block predict): {base_us:.0f} us/req")

    def service_chunk(svc, chunk, bs):
        svc.predict_batch(ua, chunk)

    service_rows = sweep_layer(
        "service", service_chunk, (1, 8, 64, 256),
        lambda: PredictionService(ModelRegistry(tmp), start=False),
        lambda svc: svc.close())

    def wire_chunk(client, chunk, bs):
        if bs == 1:
            client.predict(ua, chunk[0])
        else:
            client.predict_batch(ua, chunk)

    wire_ctxs = []

    def make_wire():
        cm = local_service(tmp)
        client = cm.__enter__()
        wire_ctxs.append(cm)
        return client

    def close_wire(client):
        wire_ctxs.pop().__exit__(None, None, None)

    wire_rows = sweep_layer("wire", wire_chunk, (1, 64, 256),
                            make_wire, close_wire)

    # simulate-backed mode: ground-truth steady-state cycles for a
    # sub-wave of the workload, measured on the simulated core through
    # its batched backend, judged against the analytic predictions
    from repro.service.batch_predictor import BatchPredictor
    bp = BatchPredictor(model, TEST_ISA, machine=machine)
    sub = blocks[:64]
    t0 = _time.perf_counter()
    sim_cycles = bp.simulate_batch(sub)
    sim_s = _time.perf_counter() - t0
    preds = bp.predict_batch(sub)
    rel = [abs(p.cycles - s) / s
           for p, s in zip(preds, sim_cycles) if s > 0]
    mean_rel = sum(rel) / max(len(rel), 1)
    print(f"  simulate-backed check: {len(sub)} blocks measured in "
          f"{sim_s * 1e3:.0f} ms (batched), mean |pred-sim|/sim = "
          f"{100 * mean_rel:.1f}%")
    emit("service_simulate_backed", sim_s * 1e6 / len(sub),
         f"mean_rel_err={mean_rel:.3f}")
    SERVICE_STATS["simulate_backed"] = {
        "blocks": len(sub), "seconds": round(sim_s, 4),
        "mean_rel_error_vs_prediction": round(mean_rel, 4)}

    tmpdir.cleanup()
    best = max(r["warm_speedup_vs_single"] for r in service_rows)
    ok = best >= 50
    print(f"  best warm-cache service-layer speedup vs uncached "
          f"single-block path: {best:.0f}x "
          f"({'meets' if ok else 'MISSES'} the >=50x target)")
    SERVICE_STATS.update({
        "n_blocks": n_blocks,
        "baseline_single_us": round(base_us, 2),
        "sweep": service_rows + wire_rows,
        "best_warm_speedup": best,
        "meets_50x_target": ok,
    })


SERVICE_SATURATION_STATS: dict = {}


def bench_service_saturation(smoke: bool = False):
    """Concurrent-client saturation of the serving tier (the PR-8 rebuild).

    Four measurements, all on the same warm bulk-wave workload:

    * **sustained throughput under concurrency** — closed-loop load via
      the replayable generator (``service/loadgen.py``) at 1 and 8
      connections, against the PR-7 one-thread-per-connection JSON server
      (the baseline) and the asyncio front door on both wires. Target:
      the front door on the binary wire sustains >=5x the baseline's warm
      predictions/sec at 8 clients.
    * **open-loop saturation curve** — fixed arrival rates from 0.5x to
      4x measured capacity; latency is charged from the scheduled arrival
      (no coordinated omission), so p99 under overload is honest. The
      admission controller must shed (typed ``Overloaded``) instead of
      queueing unboundedly.
    * **wire-format ratio** — binary vs JSON end-to-end on bulk waves,
      plus a pure codec micro-bench (encode+decode round trip). Target:
      binary >=2x the JSON framing.
    * **device-resident port bounds** — ``BatchPredictor._port_bounds``
      numpy vs the jax kernel at widening waves, asserted bit-identical.

    Correctness is gated inline: served envelopes on both wires must be
    byte-identical (canonical JSON, trace ids stripped) to the in-memory
    ``BatchPredictor`` reference, and smoke-level closed-loop load must
    not shed — violations raise, which is what the CI smoke step wants."""
    import json as _json
    import tempfile
    import time as _time
    from pathlib import Path as _Path

    from repro.core import model_io
    from repro.core.engine import Campaign
    from repro.core.isa import TEST_ISA
    from repro.core.predictor import sum_usage
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_SKL
    from repro.service import protocol
    from repro.service.batch_predictor import BatchPredictor
    from repro.service.client import ServiceClient
    from repro.service.loadgen import run_load
    from repro.service.registry import ModelRegistry
    from repro.service.server import (PredictionServer, PredictionService,
                                      ThreadedPredictionServer)
    from repro.service.workload import random_blocks

    machine = SimMachine(SIM_SKL, TEST_ISA)
    names = ["ADD_R64_R64", "IMUL_R64_R64", "MUL_R64", "ADC_R64_R64", "CMC",
             "TEST_R64_R64", "SHLD_R64_R64_I8", "MOVQ2DQ_X_X", "AESDEC_X_X",
             "PSHUFD_X_X", "PADDD_X_X", "MOV_R64_M64"]
    model = Campaign(instr_names=names).run([machine],
                                            TEST_ISA).models[machine.name]
    tmpdir = tempfile.TemporaryDirectory(prefix="uops_service_sat_")
    tmp = _Path(tmpdir.name)
    (tmp / f"{machine.name}.xml").write_text(model_io.to_xml(model, TEST_ISA))
    ua = machine.name

    wave = 24 if smoke else 64
    dur = 0.5 if smoke else 2.0
    conns = 8
    blocks = random_blocks(model, TEST_ISA, wave, seed=17, max_len=8)
    rows: list[dict] = []

    def drive(server, kind, wire, n_conns, rate=None):
        r = run_load(server.host, server.port, ua, blocks, wire=wire,
                     conns=n_conns, duration_s=dur, rate_rps=rate)
        r["server"] = kind
        rows.append(r)
        offered = f"{rate:.0f}rps" if rate else "closed"
        print(f"  {kind:>9s} {wire:>6s} conns={n_conns} load={offered:>8s} "
              f"rps={r['rps']:>7.1f} pred/s={r['predictions_per_s']:>9.1f} "
              f"p50={r['p50_ms']:.2f}ms p99={r['p99_ms']:.2f}ms "
              f"shed={r['shed']} err={r['errors']}")
        return r

    print("\n== service saturation (concurrent clients, warm bulk waves) ==")
    print(f"  wave={wave} blocks/request, {dur}s per point")

    # ---- baseline: the PR-7 threaded JSON server ----
    with ThreadedPredictionServer(
            PredictionService(ModelRegistry(tmp))) as srv:
        with ServiceClient(srv.host, srv.port, wire="json") as c:
            c.predict_batch(ua, blocks)  # warm the cache
        legacy1 = drive(srv, "threaded", "json", 1)
        legacy8 = drive(srv, "threaded", "json", conns)

    # ---- the asyncio front door, both wires ----
    svc = PredictionService(ModelRegistry(tmp))
    with PredictionServer(svc, workers=4, max_queue=64) as srv:
        # byte-identity under the served path: both wires vs the in-memory
        # reference predictor
        bp_ref = BatchPredictor(model, TEST_ISA, backend="numpy")
        expected = [{"ok": True, "uarch": ua,
                     "result": protocol.prediction_to_dict(p)}
                    for p in bp_ref.predict_batch(blocks)]
        canon_ref = _json.dumps(expected, sort_keys=True)
        with ServiceClient(srv.host, srv.port, wire="json") as cj, \
                ServiceClient(srv.host, srv.port, wire="binary") as cb:
            for _ in range(2):  # cold then warm (cached-segment path)
                ej = cj.predict_batch(ua, blocks)
                eb = cb.predict_batch(ua, blocks)
                for e in ej + eb:
                    e.pop("trace_id", None)
                if not (_json.dumps(ej, sort_keys=True) == canon_ref
                        == _json.dumps(eb, sort_keys=True)):
                    raise AssertionError(
                        "served envelopes diverge from the in-memory "
                        "BatchPredictor reference")
        print("  byte-identity: json == binary == in-memory reference OK")

        front_j1 = drive(srv, "frontdoor", "json", 1)
        front_j8 = drive(srv, "frontdoor", "json", conns)
        front_b1 = drive(srv, "frontdoor", "binary", 1)
        front_b8 = drive(srv, "frontdoor", "binary", conns)
        closed_rows = [legacy1, legacy8, front_j1, front_j8, front_b1,
                       front_b8]

        # ---- open-loop saturation sweep (binary wire) ----
        cap = max(front_b8["rps"], 1.0)
        sat_rows = []
        for f in ((0.5, 2.0) if smoke else (0.5, 0.8, 1.2, 2.0, 4.0)):
            r = drive(srv, "frontdoor", "binary", conns, rate=cap * f)
            r["offered_factor"] = f
            sat_rows.append(r)
        admission = srv.admission.stats()
        wire_counts = dict(srv.wire_counts)
        wave_cache = svc.wave_cache.stats()

    # ---- load shedding: a deliberately undersized server must shed with
    # typed Overloaded errors (bounded queue) instead of queueing forever
    svc2 = PredictionService(ModelRegistry(tmp))
    with PredictionServer(svc2, workers=1, max_queue=2) as srv:
        with ServiceClient(srv.host, srv.port, wire="json") as c:
            c.predict_batch(ua, blocks)
        shed_row = drive(srv, "tiny(w1q2)", "json", conns)
        shed_admission = srv.admission.stats()
    if shed_row["shed"] == 0:
        raise AssertionError("undersized server (1 worker, queue 2) did "
                             "not shed under 8-way load")
    if shed_admission["peak_inflight"] > 1 + 2:
        raise AssertionError(f"queue grew past its bound: {shed_admission}")

    # queue growth is bounded by construction; assert the accounting agrees
    if admission["peak_inflight"] > admission["workers"] + \
            admission["max_queue"] + 1:
        raise AssertionError(f"unbounded queue growth: {admission}")
    overloaded = [r for r in sat_rows if r.get("offered_factor", 0) >= 2.0]
    if overloaded and not smoke:
        if all(r["shed"] == 0 and r["p99_ms"] > 10 * dur * 1e3
               for r in overloaded):
            raise AssertionError("overload neither shed nor kept latency "
                                 "bounded")
    shed_at_smoke_load = sum(r["shed"] + r["errors"] for r in closed_rows)
    if shed_at_smoke_load:
        raise AssertionError(f"closed-loop (smoke-level) load shed/errored "
                             f"{shed_at_smoke_load} requests")

    # ---- framing micro-bench: server-side decode + encode per bulk wave
    # (the work the wire format imposes per warm request; the binary
    # cached-segment response path is what the server actually runs)
    packed = [protocol.instrs_to_packed(b) for b in blocks]
    req_json = _json.dumps({"op": "predict_batch", "uarch": ua,
                            "blocks": [protocol.packed_to_wire(pb)
                                       for pb in packed]},
                           separators=(",", ":"))
    req_bin = protocol.encode_predict_batch(ua, packed)
    resp_envs = expected  # trace-id-free envelopes, as the server sends
    resp_json = _json.dumps({"ok": True, "result": resp_envs},
                            separators=(",", ":"))
    pidx = {p: i for i, p in enumerate(bp_ref.port_names)}
    chunks = [protocol.encode_pred_chunk(e, pidx) for e in resp_envs]
    reps = 30 if smoke else 200

    def _codec_pass(fn):
        t0 = _time.perf_counter()
        for _ in range(reps):
            fn()
        return (_time.perf_counter() - t0) * 1e6 / reps

    def json_framing():
        msg = _json.loads(req_json)
        tuple(protocol.wire_to_packed(b) for b in msg["blocks"])
        _json.dumps({"ok": True, "result": resp_envs},
                    separators=(",", ":"))

    def bin_framing():
        protocol.decode_predict_batch(req_bin)
        protocol.encode_predict_batch_resp("t" * 16, ua,
                                           bp_ref.port_names, chunks)

    json_us = min(_codec_pass(json_framing) for _ in range(3))
    bin_us = min(_codec_pass(bin_framing) for _ in range(3))
    codec_ratio = json_us / bin_us
    print(f"  server-side framing (wave={wave}): json={json_us:.0f}us "
          f"binary={bin_us:.0f}us ratio={codec_ratio:.1f}x")
    print(f"  request bytes: json={len(req_json)} "
          f"binary={len(protocol.encode_predict_batch(ua, packed))}; "
          f"response bytes: json={len(resp_json)} binary="
          f"{len(protocol.encode_predict_batch_resp('t' * 16, ua, bp_ref.port_names, chunks))}")

    # ---- device-resident port bounds: numpy vs jax, bit-identical ----
    dev_rows = []
    try:
        bp_jax = BatchPredictor(model, TEST_ISA, backend="jax",
                                min_device_blocks=1)
    except Exception:
        bp_jax = None
    for w in ((64,) if smoke else (64, 256, 1024)):
        wb = random_blocks(model, TEST_ISA, w, seed=23, max_len=8)
        codes = [list(b) for b in wb]
        sums = {i: sum_usage(model, c) for i, c in enumerate(codes)}
        bn, np_us = _timed(lambda: bp_ref._port_bounds(sums))
        np_us = min(np_us, _timed(lambda: bp_ref._port_bounds(sums))[1])
        row = {"wave": w, "numpy_us": round(np_us, 1)}
        if bp_jax is not None:
            bp_jax._port_bounds(sums)  # compile once
            bj, jax_us = _timed(lambda: bp_jax._port_bounds(sums))
            jax_us = min(jax_us,
                         _timed(lambda: bp_jax._port_bounds(sums))[1])
            if bn != bj:
                raise AssertionError(f"device port bounds diverge from "
                                     f"numpy at wave {w}")
            row.update(jax_us=round(jax_us, 1),
                       jax_vs_numpy=round(np_us / jax_us, 2))
        dev_rows.append(row)
        print(f"  port-bounds wave={w}: numpy={row['numpy_us']}us"
              + (f" jax={row['jax_us']}us ({row['jax_vs_numpy']}x)"
                 if "jax_us" in row else " (jax unavailable)"))

    tmpdir.cleanup()

    # ---- headline gates ----
    speedup = front_b8["predictions_per_s"] / max(
        legacy8["predictions_per_s"], 1e-9)
    wire_ratio = front_b8["predictions_per_s"] / max(
        front_j8["predictions_per_s"], 1e-9)
    meets_5x = speedup >= 5.0
    meets_2x = wire_ratio >= 2.0
    print(f"  front door (binary, {conns} conns) vs PR-7 threaded server: "
          f"{speedup:.1f}x warm predictions/sec "
          f"({'meets' if meets_5x else 'MISSES'} the >=5x target)")
    print(f"  binary vs JSON framing: e2e {wire_ratio:.1f}x, codec "
          f"{codec_ratio:.1f}x ({'meets' if meets_2x else 'MISSES'} "
          f"the >=2x target)")
    emit("service_saturation_frontdoor_b8",
         1e6 / max(front_b8["predictions_per_s"], 1e-9),
         f"pred/s={front_b8['predictions_per_s']:.0f};"
         f"speedup={speedup:.1f}x;p99_ms={front_b8['p99_ms']}")
    emit("service_saturation_codec", bin_us,
         f"json_us={json_us:.0f};ratio={codec_ratio:.1f}x")

    SERVICE_SATURATION_STATS.update({
        "smoke": smoke, "wave": wave, "duration_s": dur,
        "closed_loop": closed_rows, "open_loop": sat_rows,
        "shed_demo": {"row": shed_row, "admission": shed_admission},
        "admission_after": admission, "wire_conns": wire_counts,
        "wave_cache": wave_cache,
        "codec": {"wave": wave, "json_us": round(json_us, 1),
                  "binary_us": round(bin_us, 1),
                  "ratio": round(codec_ratio, 2)},
        "device_port_bounds": dev_rows,
        "speedup_vs_threaded_at_8": round(speedup, 2),
        "binary_vs_json_e2e": round(wire_ratio, 2),
        "meets_5x_target": meets_5x,
        "meets_2x_wire_target": meets_2x,
        "served_bit_identical": True,
    })


CORPUS_EVAL_STATS: dict = {}


def bench_corpus_eval(smoke: bool = False):
    """Corpus-evaluation throughput: a seeded block corpus streamed
    through ``BatchPredictor.simulate_batch`` as fused mega-waves.
    Sweeps wave width × wave backend (numpy vs jax); each cell runs
    twice in-process so the second run sees warm lowering/jit caches
    (the first jax cell pays the cold compile)."""
    import shutil
    import tempfile

    from repro.core.characterize import characterize
    from repro.core.isa import TEST_ISA
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_UARCHES
    from repro.corpus import CorpusSpec, evaluate_corpus, generate_corpus
    from repro.corpus.store import load_manifest, read_shard
    from repro.service.protocol import parse_block

    blocks = 128 if smoke else 2048
    widths = (32, 128) if smoke else (512, 2048, 8192)
    tmp = Path(tempfile.mkdtemp(prefix="bench_corpus_"))
    try:
        spec = CorpusSpec(seed=0, blocks_per_uarch=blocks,
                          uarches=("sim_skl",),
                          shard_size=max(16, blocks // 8))
        _, gen_us = _timed(lambda: generate_corpus(tmp / "corpus", spec))
        emit("corpus_generate", gen_us / blocks, f"blocks={blocks}")

        # characterize once (numpy oracle) so every cell measures wave
        # throughput, not model inference
        man = load_manifest(tmp / "corpus")
        used = sorted({ins.spec for s in man["shards"]
                       for r in read_shard(tmp / "corpus", s)
                       for ins in parse_block(r["block"])})
        model = characterize(SimMachine(SIM_UARCHES["sim_skl"], TEST_ISA),
                             TEST_ISA, used)
        models = {"sim_skl": model}

        print("\n== corpus evaluation: fused mega-wave throughput ==")
        print(f"{'backend':8s} {'wave':>6s} {'cold_s':>8s} {'warm_s':>8s} "
              f"{'waves':>6s} {'max_w':>6s} {'blk/s':>8s}")
        rows = []
        for backend in ("numpy", "jax"):
            for width in widths:
                runs = []
                for phase in ("cold", "warm"):
                    out = tmp / f"r_{backend}_{width}_{phase}"
                    res, us = _timed(lambda out=out: evaluate_corpus(
                        tmp / "corpus", backend=backend, wave_width=width,
                        out_dir=out, resume=False, models=models))
                    runs.append((us, res))
                (cold_us, res), (warm_us, _) = runs
                ws = res["wave_stats"]
                bps = blocks / (warm_us / 1e6)
                rows.append({"backend": backend, "wave_width": width,
                             "cold_s": round(cold_us / 1e6, 3),
                             "warm_s": round(warm_us / 1e6, 3),
                             "waves": ws["waves"],
                             "max_wave_width": ws["max_wave_width"],
                             "blocks_per_s_warm": round(bps, 1)})
                print(f"{backend:8s} {width:>6d} {cold_us / 1e6:>8.3f} "
                      f"{warm_us / 1e6:>8.3f} {ws['waves']:>6d} "
                      f"{ws['max_wave_width']:>6d} {bps:>8.1f}")
                emit(f"corpus_eval_{backend}_w{width}", warm_us / blocks,
                     f"blocks={blocks};waves={ws['waves']};"
                     f"cold_s={cold_us / 1e6:.3f}")
        CORPUS_EVAL_STATS.update({"smoke": smoke, "blocks": blocks,
                                  "widths": list(widths), "rows": rows})
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def table_roofline():
    from repro.analysis.roofline import full_table, markdown_table

    rows, us = _timed(lambda: full_table(variant="cost"))
    print("\n== §Roofline (from dry-run artifacts, single-pod) ==")
    if rows:
        print(markdown_table(rows))
    else:
        print("  (no cost-variant dry-run records found — run "
              "python -m repro.launch.dryrun --all --variant cost)")
    emit("table_roofline", us, f"cells={len(rows)}")


BENCHES = {
    "table1_characterization": table1_characterization,
    "table_legacy_versions": table_legacy_versions,
    "table_throughput_defs": table_throughput_defs,
    "fig_case_aesdec": fig_case_aesdec,
    "fig_case_shld": fig_case_shld,
    "fig_case_movq2dq": fig_case_movq2dq,
    "table_multi_latency": table_multi_latency,
    "table_zero_idioms": table_zero_idioms,
    "bench_lp": bench_lp,
    "bench_simulator": bench_simulator,
    "bench_batch_sim": bench_batch_sim,
    "bench_backend_matrix": bench_backend_matrix,
    "bench_trace_overhead": bench_trace_overhead,
    "bench_fault_overhead": bench_fault_overhead,
    "bench_device_scaling": bench_device_scaling,
    "bench_characterize": bench_characterize,
    "bench_wave_fusion": bench_wave_fusion,
    "bench_campaign_cache": bench_campaign_cache,
    "bench_service_throughput": bench_service_throughput,
    "bench_service_saturation": bench_service_saturation,
    "bench_corpus_eval": bench_corpus_eval,
    "bench_hardware_corpus": bench_hardware_corpus,
    "bench_kernel_contention": bench_kernel_contention,
    "table_roofline": table_roofline,
}


def main(argv=None) -> None:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", action="append", choices=sorted(BENCHES),
                    help="run only the named benchmark(s); repeatable")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke mode: tiny wave for bench_batch_sim and "
                         "an instruction subset for bench_characterize "
                         "(other benchmarks run at full cost — combine "
                         "with --only) and results go to "
                         "benchmarks.smoke.json")
    args = ap.parse_args(argv)
    selected = args.only or list(BENCHES)

    print("name,us_per_call,derived")
    for name in selected:
        fn = BENCHES[name]
        if name in ("bench_batch_sim", "bench_backend_matrix",
                    "bench_trace_overhead", "bench_fault_overhead",
                    "bench_device_scaling", "bench_characterize",
                    "bench_service_saturation", "bench_corpus_eval"):
            fn(smoke=args.smoke)
        else:
            fn()
    print(f"\n{len(ROWS)} benchmark rows emitted.")

    out = Path(__file__).resolve().parents[1] / "experiments"
    out.mkdir(parents=True, exist_ok=True)
    payload = {
        "rows": [{"name": n, "us_per_call": round(us, 2), "derived": d}
                 for n, us, d in ROWS],
        "campaign_cache": CAMPAIGN_STATS,
        "service": SERVICE_STATS,
        "service_saturation": SERVICE_SATURATION_STATS,
        "batch_sim": BATCH_SIM_STATS,
        "backend_matrix": BACKEND_MATRIX_STATS,
        "trace_overhead": TRACE_OVERHEAD_STATS,
        "fault_overhead": FAULT_OVERHEAD_STATS,
        "device_scaling": DEVICE_SCALING_STATS,
        "characterize": CHARACTERIZE_STATS,
        "wave_fusion": WAVE_FUSION_STATS,
        "corpus_eval": CORPUS_EVAL_STATS,
    }
    if args.only or args.smoke:
        # partial/smoke runs must not clobber the full record
        path = out / "benchmarks.smoke.json"
    else:
        path = out / "benchmarks.json"
    path.write_text(json.dumps(payload, indent=1))
    print(f"JSON results (incl. cache hit-rate / speedup) -> {path}")

    # with REPRO_TRACE=1 the whole run was traced: drop the Perfetto-
    # loadable trace next to the JSON (feed it to
    # scripts/analyze.py --trace-report for the bottleneck table)
    from repro.obs import tracer as obs
    if obs.enabled():
        from repro.obs.export import write_chrome_trace
        tpath = path.parent / (path.stem + ".trace.json")
        write_chrome_trace(tpath)
        print(f"Chrome/Perfetto trace -> {tpath}")


if __name__ == "__main__":
    main()
