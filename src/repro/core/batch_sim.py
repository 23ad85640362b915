"""Batched simulated machine: whole experiment waves as one array program.

The scalar :class:`~repro.core.simulator.SimMachine` interprets one μop per
Python-loop iteration — the hot path under every inference algorithm.  This
module executes a *wave* of experiments at once: each instruction sequence
is lowered to flat integer tensors (issue cycles, port-mask ids, latencies,
occupancies, dependency producers), the wave is padded to
``(n_experiments, n_uops)``, and the dispatch/dependency recurrence runs as
a vectorized kernel.  Two backends share the lowering and packing layers:

* ``numpy`` — the baseline: a Python loop over μop *positions* with one
  vectorized step across all experiment lanes (Python overhead is
  O(max μops), not O(total μops)).
* ``jax`` — the device-resident path: the recurrence is an AOT-compiled
  ``lax.scan`` executed per shape *bucket* (see below), with the μop
  ``mask_table`` LUT kept resident on device and chunk dispatch pipelined
  against host packing (double-buffered: pack chunk k+1 while chunk k
  executes).

With no backend given, :func:`open_machine` (behind
:class:`~repro.core.simulator.SimMachine`) takes ``REPRO_SIM_BACKEND``,
else the platform's pick (:func:`default_backend`): ``jax`` on a TPU,
``numpy`` elsewhere.

Wave execution is amortized end-to-end:

* **Lowering cache** — ``_lower`` results (:class:`_Prog` tensors) are
  memoized under a content key (canonical body + unroll count), so a warm
  wave skips Python lowering entirely even when the measurement-engine
  cache missed (e.g. only the Algorithm-2 params changed).  LRU-bounded;
  hit/miss/eviction counters surface through ``engine_stats``.
* **Shape buckets** — device kernels are compiled for a small fixed set of
  ``(S, E, R)`` shapes (quarter-octave rounding: ``b`` or ``1.5b`` for
  powers of two ``b``), so the number of compilations is bounded and warm
  waves never re-trace; ``device_stats()`` exposes the compile count the
  CI probe asserts on.
* **Vectorized packing** — chunks are packed into (bucket-sized,
  double-buffered) host arrays with sliced NumPy scatters instead of a
  per-experiment Python loop, and Counters extraction is one gather per
  wave.

Bit-identity with the scalar oracle is by construction: every quantity in
the simulation (issue cycles, latencies, penalties, port-free times) is an
integer, so all kernels run in integer arithmetic and convert to the same
float values the scalar machine produces.  ``tests/test_batch_sim.py``
differential-tests every backend on all ``SIM_UARCHES`` and random ground
truths, including dispatch tie-breaks at port-count boundaries.

Lowering resolves the full dataflow up front: operand snapshots (with
partial-register stall deltas), intra-instruction temporaries, memory
cells, store-to-load forwarding, move elimination, and zero idioms all
reduce to per-μop producer row indices.  Because the measurement engine
submits ``body * n`` unrollings (Algorithm 2), lowering detects the
periodic steady state — once the machine state signature repeats at a copy
boundary, the remaining copies are not walked.  The walk appends each
body's native rows to one set of wave-wide lists; one wave pass then
converts them and *tiles* every body's remaining copies with a gather per
tensor over the whole wave, instead of per-μop Python work or per-body
NumPy calls.
"""
from __future__ import annotations

import os
import threading
import warnings

import numpy as np

from repro.core.isa import IMM, ISA
from repro.core.simulator import Counters, _implicit_reg
from repro.core.uarch import UArch
from repro.core.uarch_compile import (F_HAS_SR, F_PRESENT, TEMP_BASE,
                                      CompiledUArch, UopTableIndex,
                                      compile_uarch)
from repro.faults import plan as faults
from repro.obs import tracer as obs

# producer descriptor kinds (recipe-time)
_P_SNAP, _P_TMP, _P_MEM, _P_CUR = 0, 1, 2, 3
# write descriptor kinds
_W_TMP, _W_MEM, _W_CELL = 0, 1, 2
# recipe kinds
_K_NORMAL, _K_ZERO_NOUOP, _K_ELIM = 0, 1, 2

BACKENDS = ("numpy", "jax")

# thin-chunk scalar-oracle crossover (lanes): below this many parallel
# lanes the array program's fixed per-step dispatch cost exceeds the
# scalar interpreter it replaces.  The default is the measured crossover
# from the ``bench_batch_sim`` thin-chunk sweep (the batched kernel wins
# from 4 lanes on the reference box; see experiments/benchmarks.json,
# ``batch_sim.min_lanes_crossover``); results are bit-identical either way.
DEFAULT_MIN_LANES = 4

# lowering-cache bound (distinct (body, unroll-count) programs).  A cold
# campaign over the test ISA caches ~1,700 per machine; the bound exists so
# service-backed machines fed unbounded query streams cannot grow without
# limit.  Cached programs are views into their wave's arrays, so an entry
# holds its whole wave's rows (~4 MB for a campaign wave of ~100K rows)
# until the wave's last entry is evicted; a wave's entries enter and age
# together, so the bound holds about the waves its newest 4096 programs
# came from, plus at most the waves of entries refreshed by later hits.
DEFAULT_LOWER_CACHE = 4096


class BackendUnavailable(RuntimeError):
    """A device backend was requested but jax is not importable."""


def default_backend() -> str:
    """The wave backend the platform picks: the ``jax`` scan kernel on a
    TPU, the ``numpy`` kernel everywhere else."""
    from repro.core.device_mesh import device_platform  # noqa: PLC0415
    return "jax" if device_platform() == "tpu" else "numpy"


def open_machine(uarch: UArch, isa: ISA, backend: str | None = None,
                 table_index: UopTableIndex | None = None,
                 min_lanes: int | None = None,
                 devices=None) -> BatchSimMachine:
    """Build the batched machine on ``backend``, else the
    ``REPRO_SIM_BACKEND`` environment variable, else the platform's pick
    (:func:`default_backend`).  A device backend whose jax is not
    importable falls back to ``numpy`` with a warning; an unknown name
    raises ``ValueError``."""
    backend = (backend or os.environ.get("REPRO_SIM_BACKEND")
               or default_backend())
    if min_lanes is None:
        min_lanes = DEFAULT_MIN_LANES
    try:
        return BatchSimMachine(uarch, isa, backend=backend,
                               table_index=table_index, min_lanes=min_lanes,
                               devices=devices)
    except BackendUnavailable:
        warnings.warn(f"sim backend {backend!r} unavailable (jax not "
                      "importable); falling back to numpy", stacklevel=4)
        return BatchSimMachine(uarch, isa, backend="numpy",
                               table_index=table_index, min_lanes=min_lanes)


def _fault_key(code) -> str:
    """Content key for ``wave.kernel`` fault rules: the sequence's spec
    string, so a seeded fault follows its poisoned sequence through every
    bisection sub-wave and every backend, and ``match=`` clauses can
    target instructions by name (see :mod:`repro.faults.plan`)."""
    return ";".join(ins.spec for ins in code)


class _Plan:
    """One executable μop of a lowered instruction recipe."""
    __slots__ = ("mask_id", "lat", "blk", "vis", "prods", "sf", "sf_cell",
                 "writes", "issue_off")

    def __init__(self, mask_id, lat, blk, vis, prods, sf, sf_cell, writes,
                 issue_off):
        self.mask_id = mask_id
        self.lat = lat
        self.blk = blk
        self.vis = vis
        self.prods = prods
        self.sf = sf
        self.sf_cell = sf_cell
        self.writes = writes
        self.issue_off = issue_off


class _Recipe:
    """Lowering recipe for one concrete instruction instance."""
    __slots__ = ("kind", "dest_cells", "period", "ekey", "src_cell",
                 "dst_cell", "advance", "snapshot", "plans", "ckey")

    def __init__(self, kind, advance, snapshot=(), plans=(), dest_cells=(),
                 period=0, ekey=None, src_cell=-1, dst_cell=-1, ckey=None):
        self.kind = kind
        self.advance = advance
        self.snapshot = snapshot
        self.plans = plans
        self.dest_cells = dest_cells
        self.period = period
        self.ekey = ekey
        self.src_cell = src_cell
        self.dst_cell = dst_cell
        self.ckey = ckey           # content key (spec, regs, value_hint)


class _Prog:
    """One experiment lowered to flat int32 tensors."""
    __slots__ = ("n_rows", "issue", "mask", "lat", "blk", "vis", "prod",
                 "delta", "finals", "max_r")

    def __init__(self, n_rows, issue, mask, lat, blk, vis, prod, delta,
                 finals, max_r):
        self.n_rows = n_rows
        self.issue = issue
        self.mask = mask
        self.lat = lat
        self.blk = blk
        self.vis = vis
        self.prod = prod
        self.delta = delta
        self.finals = finals
        self.max_r = max_r


class _WaveRows:
    """A wave's native μop rows as flat Python lists: ``_lower``'s row loop
    appends every walked body's rows here, and :func:`_materialize` turns
    them into arrays once per wave.  Producer pairs are flat too:
    ``prod_end[j]`` is the end of row ``j``'s producers in ``prod``, and
    the rare nonzero deltas (partial-register stalls) are kept sparse as
    ``(delta_at, delta)`` positions in ``prod``."""
    __slots__ = ("issue", "mask", "lat", "blk", "vis", "prod", "prod_end",
                 "delta_at", "delta")

    def __init__(self):
        self.issue: list = []
        self.mask: list = []
        self.lat: list = []
        self.blk: list = []
        self.vis: list = []
        self.prod: list = []
        self.prod_end: list = []
        self.delta_at: list = []
        self.delta: list = []


class _Walk:
    """One lowered body as ``_lower``'s loop leaves it for the wave pass.

    ``start``/``n_nat``: its native rows in the :class:`_WaveRows`;
    ``tile``: ``None`` or ``(c0, c1, s0, d_rows, d_issue)`` — copies
    ``c0..c1`` are the steady-state period, occupying native rows
    ``s0..n_nat``, and every later copy repeats them shifted by ``d_rows``
    rows and ``d_issue`` issue cycles per period; ``rows``: rows of the
    whole unrolling (native plus tiled); ``cuts``: ``(copies, rows,
    finals)`` per requested copy count."""
    __slots__ = ("start", "n_nat", "tile", "rows", "cuts")

    def __init__(self, start, n_nat, tile, rows, cuts):
        self.start = start
        self.n_nat = n_nat
        self.tile = tile
        self.rows = rows
        self.cuts = cuts


def _materialize(acc: _WaveRows, walks) -> tuple:
    """The wave pass: the wave's native rows become int32 arrays once, and
    every body's steady-state copies are unrolled by one gather per tensor
    over the whole wave.  The output is laid out body after body, each as
    segments of rows — its native rows (``q = 0``), then one segment per
    period ``q = 1, 2, …`` taking native rows ``s0..`` shifted by ``q``
    periods: ``q·d_issue`` issue cycles, and ``q·d_rows`` rows on every real
    producer.  Returns one ``{copies: _Prog}`` per walk, in order, and the
    rows written.

    Each :class:`_Prog` is a view into the wave's arrays (shorter cuts are
    prefixes of the same rows: causality); what that costs the lowering
    cache is at ``DEFAULT_LOWER_CACHE``."""
    i32 = np.int32
    # int32 throughout: every simulated quantity fits (cycles, rows, counts
    # < 2^31 - 1 — the device kernels reserve INT32_MAX as the
    # disallowed-port dispatch sentinel) and runs int32 natively
    issue = np.array(acc.issue, i32)
    mask = np.array(acc.mask, i32)
    lat = np.array(acc.lat, i32)
    blk = np.array(acc.blk, i32)
    vis = np.array(acc.vis, i32)
    n, B = issue.size, len(walks)
    ends = np.array(acc.prod_end, np.int64)
    width = np.diff(ends, prepend=0)            # producers per native row
    starts = np.array([w.start for w in walks], np.int64)
    n_nat = np.array([w.n_nat for w in walks], np.int64)
    # each body's read width: its widest native row, at least 1
    max_r = np.ones(B, np.int64)
    real = np.flatnonzero(n_nat)
    if real.size:
        max_r[real] = np.maximum(np.maximum.reduceat(width, starts[real]), 1)
    R = int(max_r.max(initial=1))
    prod = np.full(n * R, -1, i32)
    delta = np.zeros(n * R, i32)
    if n and ends[-1]:
        at = np.arange(ends[-1]) + np.repeat(np.arange(0, n * R, R)
                                             - (ends - width), width)
        prod[at] = np.array(acc.prod, i32)
        if acc.delta_at:
            delta[at[np.array(acc.delta_at, np.int64)]] = np.array(
                acc.delta, i32)
    prod = prod.reshape(n, R)
    delta = delta.reshape(n, R)
    # segments: per body its native rows, then `full` periods of `per` rows
    # and a last partial one of `left` rows
    lens = np.array([w.rows for w in walks], np.int64)
    s0 = np.zeros(B, np.int64)
    d_rows = np.zeros(B, np.int64)
    d_issue = np.zeros(B, np.int64)
    for b, w in enumerate(walks):
        if w.tile is not None:
            _, _, s0[b], d_rows[b], d_issue[b] = w.tile
    per = np.maximum(d_rows, 1)        # d_rows 0: nothing is tiled
    full, left = np.divmod(lens - n_nat, per)
    nseg = 1 + full + (left > 0)
    sb = np.repeat(np.arange(B), nseg)                  # segment -> body
    q = np.arange(nseg.sum()) - np.repeat(np.cumsum(nseg) - nseg, nseg)
    seg_len = np.where(q == 0, n_nat[sb],
                       np.where(q <= full[sb], per[sb], left[sb]))
    seg_src = starts[sb] + np.where(q == 0, 0, s0[sb])
    src = np.arange(int(lens.sum())) + np.repeat(
        seg_src - (np.cumsum(seg_len) - seg_len), seg_len)
    issue = issue.take(src)
    issue += np.repeat((q * d_issue[sb]).astype(i32), seg_len)
    prod = prod.take(src, axis=0)
    np.add(prod, np.repeat((q * d_rows[sb]).astype(i32), seg_len)[:, None],
           out=prod, where=prod >= 0)
    mask, lat, blk, vis = (mask.take(src), lat.take(src), blk.take(src),
                           vis.take(src))
    delta = delta.take(src, axis=0)
    made = []
    for o, r, w in zip((np.cumsum(lens) - lens).tolist(), max_r.tolist(),
                       walks):
        made.append({c: _Prog(m, issue[o:o + m], mask[o:o + m],
                              lat[o:o + m], blk[o:o + m], vis[o:o + m],
                              prod[o:o + m, :r], delta[o:o + m, :r], fin, r)
                     for c, m, fin in w.cuts})
    return made, src.size


def _body_period(ids) -> int:
    """Smallest p with ``ids == ids[:p] * k`` (object identities — the
    engine's ``body * n`` unrollings share instruction objects)."""
    n = len(ids)
    if n < 2:
        return n
    first = ids[0]
    for p in range(1, n // 2 + 1):
        if ids[p] == first and n % p == 0 and ids[p:] == ids[:-p]:
            return p
    return n


def _code_period(code) -> int:
    """:func:`_body_period` directly over the instruction list: the slice
    compare runs at C speed with CPython's identity short-circuit (the
    engine's ``body * n`` unrollings share objects), and a content-equal
    fallback is harmless — recipes key on content.  This runs per sequence
    on the wave hot path, ahead of every lowering-cache probe."""
    n = len(code)
    if n < 2:
        return n
    first = code[0]
    for p in range(1, n // 2 + 1):
        if code[p] is first and n % p == 0 and code[p:] == code[:-p]:
            return p
    return n


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length()


def _bucket(n: int, lo: int) -> int:
    """Smallest value >= n of the form ``lo * 2**k`` or ``1.5 * lo * 2**k``
    (quarter-octave shape buckets: at most ~33% padding, O(log n) distinct
    buckets, so device kernels compile a bounded number of times)."""
    b = lo
    while b < n:
        h = b + b // 2
        if h >= n:
            return h
        b *= 2
    return b


class _ChunkPack:
    """One packed chunk: bucket-shaped input tensors + extraction metadata.

    ``vis``/``valid`` live alongside the kernel inputs; only they (and the
    scatter targets) are re-zeroed when a device buffer set is reused —
    every other cell of a reused buffer is gated off by ``valid`` in the
    kernels, so stale data cannot perturb results."""
    __slots__ = ("chunk", "lane_progs", "S", "E", "R", "issue", "mask",
                 "lat", "blk", "valid", "prod", "delta", "vis")

    def __init__(self, chunk, lane_progs, S, E, R, issue, mask, lat, blk,
                 valid, prod, delta, vis):
        self.chunk = chunk
        self.lane_progs = lane_progs
        self.S = S
        self.E = E
        self.R = R
        self.issue = issue
        self.mask = mask
        self.lat = lat
        self.blk = blk
        self.valid = valid
        self.prod = prod
        self.delta = delta
        self.vis = vis


class BatchSimMachine:
    """Measurable black box executing waves of sequences as array programs.

    Same observable contract as :class:`~repro.core.simulator.SimMachine`
    (cycles + per-port μop counts, including harness overhead), plus
    :meth:`run_batch` — and bit-identical results to the scalar oracle on
    both backends (``numpy``, ``jax``)."""

    counters_available = True

    def __init__(self, uarch: UArch, isa: ISA, backend: str = "numpy",
                 table_index: UopTableIndex | None = None,
                 min_lanes: int = DEFAULT_MIN_LANES,
                 lower_cache_entries: int | None = DEFAULT_LOWER_CACHE,
                 devices=None):
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}")
        if backend != "numpy" and _jax() is None:
            raise BackendUnavailable(f"{backend} backend requested but jax "
                                     "is not importable")
        self.uarch = uarch
        self.isa = isa
        self.name = uarch.name
        self.ports = uarch.ports
        self.backend = backend
        # device placement spec for the jax backend: None (the
        # REPRO_SIM_DEVICES env knob, default all available devices), an
        # integer count, "all", or an explicit jax device sequence —
        # resolved lazily by core/device_mesh when the device executor is
        # first built.  More than one resolved device shards every wave's
        # lanes across a 1-D ``lanes`` mesh; a single device (the normal
        # CPU case) keeps the single-device path, bit-identical either way
        self.devices = devices
        # a padded chunk with fewer lanes than this runs on the scalar
        # oracle instead: the array program's fixed per-step dispatch cost
        # only amortizes across enough parallel lanes (results are
        # bit-identical either way; set 1 to force the kernel)
        self.min_lanes = min_lanes
        self._comp: CompiledUArch = compile_uarch(uarch, isa, table_index)
        self._cells: dict = {}          # register name -> cell id
        self._recipes_by_key: dict = {}
        self._scalar = None             # lazy scalar fallback for thin chunks
        # lowering cache: (body content key, unroll count) -> _Prog (LRU)
        self._lower_cache: dict = {}
        self._lower_max = lower_cache_entries
        self.lowering_stats = {"hits": 0, "misses": 0, "evictions": 0}
        # backend degradation counters: "<from>-><to>" -> chunks rerouted
        # down the backend chain (jax -> numpy -> scalar oracle)
        # after a kernel-path failure.  Results stay bit-identical (every
        # backend computes the same integers); the engine snapshots these
        # through degraded_stats() into EngineStats.degraded_chunks.
        self.degraded: dict = {}
        self._device = None             # lazy _DeviceExec (jax)
        # guards the machine's shared mutable host state (lowering-cache
        # LRU, recipe memo, lazy device/scalar init) across concurrent
        # run_batch callers; slot leasing has its own lock in _DeviceExec
        self._host_lock = threading.Lock()

    # ------------------------------------------------------------------
    def run(self, code) -> Counters:
        return self.run_batch([code])[0]

    def set_devices(self, devices) -> None:
        """Adopt a device placement (count, ``"all"``, or an explicit jax
        device sequence — see :mod:`repro.core.device_mesh`).  The device
        executor is rebuilt on the next wave; results are bit-identical
        for every placement.  ``Campaign.run`` uses this to place each
        machine on a disjoint device subset."""
        with self._host_lock:
            self.devices = devices
            self._device = None

    def device_stats(self) -> dict:
        """Device-kernel telemetry: compile count (the CI recompile probe
        asserts ``compiles <= len(buckets)``), kernel dispatches, the
        shape buckets seen so far, the resolved device placement, and
        per-device compile/kernel-call/lane counters (``per_device``,
        keyed by jax device id — cross-device recompiles show up here).
        Empty for the numpy backend."""
        if self._device is None:
            return {}
        return self._device.stats()

    def degraded_stats(self) -> dict:
        """Per-transition backend degradation counters
        (``{"jax->numpy": 2, ...}``) — empty when no chunk has ever been
        rerouted, which is the overwhelmingly common case."""
        with self._host_lock:
            return dict(self.degraded)

    def _note_degraded(self, frm: str, to: str, chunks: int,
                       exc: BaseException) -> None:
        key = f"{frm}->{to}"
        with self._host_lock:
            self.degraded[key] = self.degraded.get(key, 0) + chunks
        obs.instant("wave.degraded", transition=key, chunks=chunks,
                    error=f"{type(exc).__name__}: {exc}")
        warnings.warn(f"{self.name}: {chunks} chunk(s) degraded {key} "
                      f"after {type(exc).__name__}: {exc}", stacklevel=2)

    def run_batch(self, codes, kernel_lock=None) -> list:
        """Execute each sequence once; one :class:`Counters` per sequence,
        in submission order.

        ``kernel_lock`` (optional ``threading.Lock``) serializes the
        GIL-bound kernels — the numpy backend's Python-stepped loop and
        the scalar-oracle fallback — which thrash when interleaved across
        threads; host lowering and packing always run outside it.  The
        device backends do not take it: their compiled kernels release
        the GIL and are scheduled by the machine's device pool, and
        dispatch serializes on the executor's per-device-subset lock
        (:func:`repro.core.device_mesh.dispatch_lock`) so machines on
        disjoint device subsets overlap (see ``WaveScheduler``).

        Concurrent ``run_batch`` calls on one machine instance are safe —
        the lowering cache/recipe memo and the device buffer-slot leasing
        are mutex-guarded — but they serialize on host lowering; the
        intended topology is one caller per machine (campaign workers own
        distinct machines and overlap only *across* machines).

        With tracing on (``REPRO_TRACE=1``, see :mod:`repro.obs`) each
        wave emits a ``wave.run_batch`` span with per-phase children
        (``wave.lower`` / ``wave.pack`` / ``wave.kernel`` (host kernels) /
        ``wave.dispatch`` / ``wave.result_wait`` / ``wave.extract``) and
        lock-wait spans (``wave.lock_wait`` / ``wave.dispatch_lock_wait``)
        measured separately from the work they guard.  ``wave.run_batch``
        carries ``chunks`` (the length chunks that run batched) and
        ``scalar_lanes`` (lanes of thin chunks run on the scalar oracle);
        ``wave.dispatch`` carries the device kernels' ``steps`` (serial
        scan steps),
        ``slots`` (lane-steps, padding included) and ``rows`` (real μop
        rows).  Traced and untraced waves dispatch the same way; the
        kernels' device time is in the profiler trace."""
        with obs.span("wave.run_batch", lanes=len(codes),
                      backend=self.backend) as span:
            return self._run_batch(codes, kernel_lock, span)

    def _run_batch(self, codes, kernel_lock=None, span=obs.NULL_SPAN) -> list:
        codes = [list(c) for c in codes]
        out: list = [None] * len(codes)
        # chunk by similar length so short sequences don't pay for the
        # longest experiment's padded steps; thin chunks go scalar
        order = sorted(range(len(codes)), key=lambda i: -len(codes[i]))
        chunks: list = []
        chunk: list = []
        chunk_max = 0
        for i in order:
            if chunk and len(codes[i]) * 4 < chunk_max:
                chunks.append(chunk)
                chunk, chunk_max = [], 0
            if not chunk:
                chunk_max = max(len(codes[i]), 1)
            chunk.append(i)
        if chunk:
            chunks.append(chunk)
        batched = [c for c in chunks if len(c) >= self.min_lanes]
        thin = [i for c in chunks if len(c) < self.min_lanes for i in c]
        span.set(chunks=len(batched), scalar_lanes=len(thin))
        if thin:
            self._chunk_scalar(thin, codes, out, kernel_lock)
        if not batched:
            return out
        progs = self._lower_wave(codes, batched)
        if self.backend == "numpy":
            for c in batched:
                try:
                    self._chunk_numpy(c, codes, progs, out, kernel_lock)
                except Exception as exc:
                    self._note_degraded("numpy", "scalar", 1, exc)
                    self._chunk_scalar(c, codes, out, kernel_lock,
                                       span="wave.degraded")
        else:
            try:
                self._run_device(batched, codes, progs, out, kernel_lock)
            except Exception as exc:
                self._degrade_device(batched, codes, progs, out,
                                     kernel_lock, exc)
        return out

    def _ensure_scalar(self):
        with self._host_lock:
            if self._scalar is None:
                from repro.core.simulator import SimMachine  # noqa: PLC0415
                self._scalar = SimMachine(self.uarch, self.isa)
            return self._scalar

    def _chunk_scalar(self, idxs, codes, out, kernel_lock,
                      span: str = "wave.scalar") -> None:
        """Run ``idxs`` on the scalar oracle — the thin-chunk path, and
        the terminal rung of the backend degradation chain."""
        sim = self._ensure_scalar()
        # wait_lock(None) degrades to a no-op, so both lock topologies
        # share one code path; acquisition wait is traced separately
        with obs.span(span, thin=len(idxs)), \
                obs.wait_lock(kernel_lock, "wave.lock_wait"):
            if faults.active():
                faults.check_wave("wave.kernel",
                                  [_fault_key(codes[i]) for i in idxs],
                                  backend="scalar")
            for i in idxs:
                out[i] = sim.run(codes[i])

    def _chunk_numpy(self, c, codes, progs, out, kernel_lock) -> None:
        """Pack + host-kernel + extract for one chunk (the numpy backend's
        per-chunk unit of work, also the numpy rung of the degradation
        chain for device-backend failures)."""
        with obs.span("wave.pack", lanes=len(c)):
            if faults.active():
                faults.check("wave.pack", backend="numpy")
            pk = self._pack_chunk(c, progs)
        if pk.S == 0:
            self._fill_empty(c, out)
            return
        with obs.wait_lock(kernel_lock, "wave.lock_wait"), \
                obs.span("wave.kernel", lanes=pk.E, steps=pk.S):
            if faults.active():
                faults.check_wave("wave.kernel",
                                  [_fault_key(codes[i]) for i in c],
                                  backend="numpy")
            done, counts = self._kernel_numpy(pk)
        with obs.span("wave.extract", lanes=len(c)):
            self._extract(pk, done.T, counts, out)

    def _degrade_device(self, batched, codes, progs, out, kernel_lock,
                        exc: BaseException) -> None:
        """Backend degradation chain: on a device-path failure, re-run
        every chunk that never produced results on the next backend down
        (jax -> numpy -> scalar oracle).  Results stay bit-identical —
        each backend computes the same integers — so a degraded wave is
        correct, just slower; reroutes are counted per transition
        (``degraded_stats()``).  A wave that fails even the scalar oracle
        re-raises, handing the measurement engine's bisecting retry the
        job of isolating the poison experiment."""
        remaining = [c for c in batched if any(out[i] is None for i in c)]
        if not remaining:
            return
        self._note_degraded(self.backend, "numpy", len(remaining), exc)
        for c in remaining:
            try:
                self._chunk_numpy(c, codes, progs, out, kernel_lock)
            except Exception as e3:
                self._note_degraded("numpy", "scalar", 1, e3)
                self._chunk_scalar(c, codes, out, kernel_lock,
                                   span="wave.degraded")

    # ------------------------------------------------------------------
    # lowering cache: content-addressed _Prog tensors
    # ------------------------------------------------------------------
    def _lower_wave(self, codes, batched) -> dict:
        """Lower every batched sequence, serving repeat bodies from the
        content-addressed lowering cache.  Sequences sharing one body
        (Algorithm 2 submits the same body at two unroll counts) lower the
        longest *missing* count once; shorter unrollings are prefix views
        of the same tensors (causality).  Holds the machine's host lock:
        the cache LRU (pop/reinsert/evict) and the recipe memo are shared
        mutable state across concurrent ``run_batch`` callers.  Traced as
        a ``wave.lower`` span carrying this wave's cache hit/miss delta,
        the rows the lowering loop walked (``rows_native``) and the rows
        the wave pass materialized (``rows_out``)."""
        stats = self.lowering_stats
        h0, m0 = stats["hits"], stats["misses"]
        with obs.span("wave.lower",
                      lanes=sum(len(c) for c in batched)) as sp, \
                self._host_lock:
            progs, rows_native, rows_out = self._lower_wave_locked(codes,
                                                                   batched)
            sp.set(hits=stats["hits"] - h0, misses=stats["misses"] - m0,
                   rows_native=rows_native, rows_out=rows_out)
        return progs

    def _lower_wave_locked(self, codes, batched) -> tuple:
        """Group the wave's sequences by body, serve what the cache holds,
        walk every missing body once at its longest missing count
        (``_lower``, into one :class:`_WaveRows`), then materialize all of
        them in one wave pass (:func:`_materialize`).  Returns the
        programs by sequence index, the rows the loop walked and the rows
        the wave pass wrote."""
        by_id: dict = {}
        groups: dict = {}
        for c in batched:
            for i in c:
                code = codes[i]
                if code:
                    p = _code_period(code)
                    body_ck = tuple(self._recipe(ins, by_id).ckey
                                    for ins in code[:p])
                    key = (p, body_ck)
                    nc = len(code) // p
                else:
                    key, nc = (0, ()), 0
                groups.setdefault(key, []).append((i, nc))
        cache = self._lower_cache
        stats = self.lowering_stats
        acc = _WaveRows()
        found: list = []        # per group: (body_ck, members, programs)
        walks: list = []
        walked: list = []       # per walk: its group's programs
        for (p, body_ck), members in groups.items():
            cuts = sorted({nc for _, nc in members})
            have: dict = {}
            missing: list = []
            for nc in cuts:
                hit = cache.pop((body_ck, nc), None)   # pop: LRU touch
                if hit is None:
                    missing.append(nc)
                else:
                    have[nc] = hit
            stats["hits"] += len(have)
            if missing:
                stats["misses"] += len(missing)
                rep_i = max(members, key=lambda t: t[1])[0]
                rep_code = codes[rep_i][:p * missing[-1]]
                walks.append(self._lower(rep_code, by_id, acc, missing, p))
                walked.append(have)
            # reinsert as newest; a missing cut's slot is filled after the
            # wave pass (no later group of this wave probes this body)
            for nc in cuts:
                cache[(body_ck, nc)] = have.get(nc)
            if self._lower_max is not None:
                while len(cache) > self._lower_max:
                    cache.pop(next(iter(cache)))       # oldest entry
                    stats["evictions"] += 1
            found.append((body_ck, members, have))
        rows_out = 0
        if walks:
            made, rows_out = _materialize(acc, walks)
            for have, progs_w in zip(walked, made):
                have.update(progs_w)
        progs: dict = {}
        for body_ck, members, have in found:
            for i, nc in members:
                progs[i] = have[nc]
            for nc in have:
                key = (body_ck, nc)
                if key in cache:        # not evicted: keeps its LRU place
                    cache[key] = have[nc]
        return progs, len(acc.issue), rows_out

    # ------------------------------------------------------------------
    # recipes: per concrete instruction instance, content-memoized
    # ------------------------------------------------------------------
    def _cell(self, name: str) -> int:
        c = self._cells.get(name)
        if c is None:
            c = self._cells[name] = len(self._cells)
        return c

    def _recipe(self, ins, by_id: dict) -> _Recipe:
        r = by_id.get(id(ins))
        if r is None:
            key = (ins.spec, tuple(sorted(ins.regs.items())), ins.value_hint)
            r = self._recipes_by_key.get(key)
            if r is None:
                r = self._build_recipe(ins)
                r.ckey = key
                self._recipes_by_key[key] = r
            by_id[id(ins)] = r
        return r

    def _build_recipe(self, ins) -> _Recipe:
        comp = self._comp
        idx = comp.index.idx[ins.spec]       # KeyError like isa[...]
        info = comp.index.specs[idx]
        if not comp.flags[idx] & F_PRESENT:  # KeyError like ua.behaviors[..]
            raise KeyError(ins.spec)
        regs = dict(ins.regs)
        for nm, ot in zip(info.op_names, info.op_otype):
            if nm not in regs and ot != IMM:
                regs[nm] = _implicit_reg(nm, ot)
        same = (len(info.same_reg_ops) >= 2
                and len({regs[n] for n in info.same_reg_ops}) == 1)
        use_sr = same and bool(comp.flags[idx] & F_HAS_SR)
        zero_nouop = bool(comp.sr_zero_nouop[idx] if use_sr
                          else comp.zero_nouop[idx])
        elim_period = int(comp.sr_elim_period[idx] if use_sr
                          else comp.elim_period[idx])
        div_extra = int(comp.sr_divider_extra[idx] if use_sr
                        else comp.divider_extra[idx])
        zero = info.zero_idiom and same
        if zero and zero_nouop:
            return _Recipe(_K_ZERO_NOUOP, 0, dest_cells=tuple(
                self._cell(regs[d]) for d in info.dest_names))
        off, cnt = comp.behavior_rows(idx, same)
        extra = div_extra if (ins.value_hint == "high" and not zero) else 0
        vis = 0 if zero else 1
        ignore_reads = zero
        snapshot = tuple((self._cell(regs.get(nm, nm)), chk, w)
                         for nm, chk, w in info.snapshot)
        snap_pos = {nm: i for i, (nm, _, _) in enumerate(info.snapshot)}
        syms = comp.syms[idx]
        plans = []
        issue_off = 0
        for j in range(cnt):
            row = off + j
            if comp.port_mask[row] == 0:   # 0-port μop: scalar skips it
                continue
            names = []
            for slot in comp.reads[row]:
                if slot < 0:
                    break
                names.append(info.op_names[slot] if slot < TEMP_BASE
                             else syms[slot - TEMP_BASE])
            prods = []
            if not ignore_reads:
                for nm in names:
                    if nm.startswith("%"):
                        prods.append((_P_TMP, nm))
                    elif nm in info.mem_read and info.mem_read[nm]:
                        prods.append((_P_MEM, self._cell(regs[nm])))
                    elif nm in snap_pos:
                        prods.append((_P_SNAP, snap_pos[nm]))
                    else:
                        prods.append((_P_CUR,
                                      self._cell(regs.get(nm, nm))))
            sf = any(nm in info.mem_read and info.mem_read[nm]
                     for nm in names)
            sf_cell = next((self._cell(regs[nm]) for nm in names
                            if nm in info.mem_read), -1)
            writes = []
            for slot in comp.writes[row]:
                if slot < 0:
                    break
                nm = (info.op_names[slot] if slot < TEMP_BASE
                      else syms[slot - TEMP_BASE])
                if nm.startswith("%"):
                    writes.append((_W_TMP, nm, None))
                elif nm in info.mem_read:
                    writes.append((_W_MEM, self._cell(regs[nm]), None))
                else:
                    try:
                        w = info.op_width[info.op_names.index(nm)]
                    except ValueError:
                        w = None
                    writes.append((_W_CELL, self._cell(regs.get(nm, nm)), w))
            occ = int(comp.occupancy[row]) + extra
            plans.append(_Plan(int(comp.mask_id[row]),
                               int(comp.latency[row]) + extra,
                               occ if occ > 1 else 1, vis, tuple(prods),
                               sf, sf_cell, tuple(writes), issue_off))
            issue_off += 1
        if info.may_eliminate and elim_period and not zero:
            return _Recipe(_K_ELIM, cnt, snapshot, tuple(plans),
                           period=elim_period, ekey=ins.spec,
                           src_cell=self._cell(regs[info.elim_src]),
                           dst_cell=self._cell(regs[info.dest_names[0]]))
        return _Recipe(_K_NORMAL, cnt, snapshot, tuple(plans))

    # ------------------------------------------------------------------
    # lowering: sequence -> flat tensors (with periodic-steady-state tiling)
    # ------------------------------------------------------------------
    def _lower(self, code, by_id: dict, acc: _WaveRows, cuts=None,
               period=None) -> _Walk:
        """Walk ``code`` (= body * ncopies) μop by μop, appending its native
        rows to the wave's ``acc``, until the machine state's signature
        repeats at a copy boundary: from there on the copies are periodic,
        and the walk only describes them (``_Walk.tile``) for the wave pass
        (:func:`_materialize`) to unroll.  Builds no arrays.  Producer rows
        are body-local.  ``cuts`` are the copy counts to return programs
        for (default: all copies); each gets its row count and final-writer
        rows."""
        comp = self._comp
        width = comp.issue_width
        penalty = comp.partial_stall_penalty
        sfl = comp.store_forward_latency
        n = len(code)
        p = period if period is not None else (
            _body_period([id(x) for x in code]) if n else 0)
        ncopies = n // p if p else 0
        if cuts is None:
            cuts = [ncopies]
        body = [self._recipe(ins, by_id) for ins in code[:p]]

        lw: dict = {}       # cell -> producing row
        wd: dict = {}       # cell -> width of last write
        ml: dict = {}       # mem cell -> producing (store) row
        ms: set = set()     # mem cells with a store seen
        ec: dict = {}       # elim spec key -> instance count
        ecp: dict = {}      # elim spec key -> period
        issue_l = acc.issue
        base = len(issue_l)     # this body's row 0 in the wave's lists
        issue_a = issue_l.append
        mask_a = acc.mask.append
        lat_a = acc.lat.append
        blk_a = acc.blk.append
        vis_a = acc.vis.append
        prod_l = acc.prod
        prod_a = prod_l.append
        end_a = acc.prod_end.append
        dat_a = acc.delta_at.append
        dval_a = acc.delta.append
        uop_counter = 0

        sig_map: dict = {}
        snaps: list = []    # per copy boundary: (rows, uops, lw, ml)
        tile = None

        def signature():
            nr = len(issue_l) - base
            return (uop_counter % width,
                    tuple(sorted((c, nr - r) for c, r in lw.items())),
                    tuple(sorted(wd.items())),
                    tuple(sorted((c, nr - r) for c, r in ml.items())),
                    tuple(sorted(ms)),
                    tuple(sorted((k, c % ecp[k]) for k, c in ec.items())))

        for i in range(ncopies):
            if ncopies > 1:
                sig = signature()
                c0 = sig_map.get(sig)
                if c0 is not None:
                    tile = (c0, i)
                    break
                sig_map[sig] = i
            snaps.append((len(issue_l) - base, uop_counter, dict(lw),
                          dict(ml)))
            for r in body:
                k = r.kind
                if k == _K_ZERO_NOUOP:
                    for c in r.dest_cells:
                        lw.pop(c, None)
                    continue
                if k == _K_ELIM:
                    c = ec.get(r.ekey, 0)
                    ec[r.ekey] = c + 1
                    ecp[r.ekey] = r.period
                    if c % r.period:
                        s = lw.get(r.src_cell, -1)
                        if s < 0:
                            lw.pop(r.dst_cell, None)
                        else:
                            lw[r.dst_cell] = s
                        continue
                svals = [(lw.get(cell, -1),
                          penalty if (chk and w > wd.get(cell, 64)) else 0)
                         for cell, chk, w in r.snapshot]
                tmp: dict = {}
                for pl in r.plans:
                    row = len(issue_l) - base
                    for kind, a in pl.prods:
                        if kind == _P_SNAP:
                            pp, dd = svals[a]
                            if dd:
                                dat_a(len(prod_l))
                                dval_a(dd)
                            prod_a(pp)
                        elif kind == _P_TMP:
                            prod_a(tmp.get(a, -1))
                        elif kind == _P_CUR:
                            prod_a(lw.get(a, -1))
                        else:   # _P_MEM: reg base + memory value
                            prod_a(lw.get(a, -1))
                            prod_a(ml.get(a, -1))
                    end_a(len(prod_l))
                    lat = pl.lat
                    if pl.sf and pl.sf_cell in ms:
                        lat = min(lat, sfl)
                    issue_a((uop_counter + pl.issue_off) // width)
                    mask_a(pl.mask_id)
                    lat_a(lat)
                    blk_a(pl.blk)
                    vis_a(pl.vis)
                    for wk, a, b in pl.writes:
                        if wk == _W_TMP:
                            tmp[a] = row
                        elif wk == _W_MEM:
                            ml[a] = row
                            ms.add(a)
                        else:
                            lw[a] = row
                            if b is not None:
                                wd[a] = b
                uop_counter += r.advance
        snaps.append((len(issue_l) - base, uop_counter, dict(lw), dict(ml)))

        n_nat = len(issue_l) - base
        desc = None
        if tile is not None:
            c0, c1 = tile
            s0, u0 = snaps[c0][0], snaps[c0][1]
            d_rows, d_uops = n_nat - s0, uop_counter - u0
            assert d_uops % width == 0
            desc = (c0, c1, s0, d_rows, d_uops // width)
            per = c1 - c0

        def boundary(b):
            """(rows, row shift, reg cells, mem cells) after ``b`` copies."""
            if tile is None or b <= tile[1]:
                rows_b, _, lwb, mlb = snaps[b]
                return rows_b, 0, lwb, mlb
            qb, rb = divmod(b - c0, per)
            rows_b = s0 + qb * d_rows + (snaps[c0 + rb][0] - s0)
            return rows_b, qb * d_rows, snaps[c0 + rb][2], snaps[c0 + rb][3]

        made: list = []
        for b in cuts:
            rows_b, sh, lwb, mlb = boundary(b)
            fin = sorted({r + sh for r in lwb.values()}
                         | {r + sh for r in mlb.values()})
            made.append((b, rows_b, np.array(fin, np.int64)))
        return _Walk(base, n_nat, desc, boundary(ncopies)[0], made)

    # ------------------------------------------------------------------
    # packing: chunk -> bucket tensors (vectorized NumPy scatter)
    # ------------------------------------------------------------------
    def _pack_chunk(self, chunk, progs, bufs=None) -> _ChunkPack:
        """Pack a chunk's lowered programs into wave tensors with sliced
        scatters (one concatenate + one fancy-index assignment per tensor,
        not a per-experiment Python loop).

        ``bufs`` reuses a device bucket buffer set in *lane-major*
        ``(E, S)`` layout — the scatter then writes each lane's rows to
        consecutive addresses, and the device kernel transposes once on
        device instead of the host scattering strided.  Only ``valid`` and
        ``vis`` are re-zeroed on reuse; every other stale cell is gated
        off by ``valid`` in the device kernels.  ``None`` allocates fresh
        exact-shape ``(S, E)`` arrays for the numpy kernel (which walks μop
        rows and relies on zeroed padding)."""
        E0 = len(chunk)
        gs = [progs[i] for i in chunk]
        S0 = max(g.n_rows for g in gs)
        R0 = max(g.max_r for g in gs)
        lane_major = bufs is not None
        if bufs is None:
            S, E, R = S0, E0, max(R0, 1)
            issue = np.zeros((S, E), np.int32)
            mask = np.zeros((S, E), np.int32)
            lat = np.zeros((S, E), np.int32)
            blk = np.zeros((S, E), np.int32)
            valid = np.zeros((S, E), bool)
            prod = np.full((S, E, R), -1, np.int32)
            delta = np.zeros((S, E, R), np.int32)
            vis = np.zeros((E, S), np.int32)
        else:
            issue, mask, lat, blk, valid, prod, delta, vis = bufs
            E, S = issue.shape
            R = prod.shape[2]
            valid[:] = False
            vis[:] = 0
        pk = _ChunkPack(chunk, gs, S0, E0, R0, issue, mask, lat, blk,
                        valid, prod, delta, vis)
        if S0 == 0:
            return pk
        if lane_major:
            # lane-major: one contiguous slice copy per lane per tensor —
            # every write lands on consecutive addresses
            for e, g in enumerate(gs):
                m = g.n_rows
                if not m:
                    continue
                issue[e, :m] = g.issue
                mask[e, :m] = g.mask
                lat[e, :m] = g.lat
                blk[e, :m] = g.blk
                valid[e, :m] = True
                vis[e, :m] = g.vis
                r = g.max_r
                prod[e, :m, :r] = g.prod
                delta[e, :m, :r] = g.delta
                if r < R:
                    # the kernels read ALL R producer columns of a valid
                    # row — stale values from a previous occupant of this
                    # reused buffer are only row-gated, never column-gated
                    prod[e, :m, r:] = -1
                    delta[e, :m, r:] = 0
            return pk
        # row-major (numpy kernel): one concatenate + fancy scatter per
        # tensor instead of E strided per-lane column writes
        lens = np.fromiter((g.n_rows for g in gs), np.int64, E0)
        total = int(lens.sum())
        if not total:
            return pk
        cols = np.repeat(np.arange(E0), lens)
        starts = np.concatenate(([0], np.cumsum(lens)[:-1]))
        rows = np.arange(total) - np.repeat(starts, lens)
        issue[rows, cols] = np.concatenate([g.issue for g in gs])
        mask[rows, cols] = np.concatenate([g.mask for g in gs])
        lat[rows, cols] = np.concatenate([g.lat for g in gs])
        blk[rows, cols] = np.concatenate([g.blk for g in gs])
        valid[rows, cols] = True
        for e, g in enumerate(gs):          # vis is lane-major everywhere
            if g.n_rows:
                vis[e, :g.n_rows] = g.vis
        # producers: lanes grouped by their program's read width, one
        # sliced scatter per distinct width (few in practice)
        widths = {g.max_r for g in gs if g.n_rows}
        for r in sorted(widths):
            if len(widths) == 1:
                m = slice(None)
                sel = [bool(g.n_rows) for g in gs]
            else:
                sel = [g.max_r == r and g.n_rows > 0 for g in gs]
                m = np.asarray(sel, bool)[cols]
            prod[rows[m], cols[m], :r] = np.concatenate(
                [g.prod for g, s in zip(gs, sel) if s])
            delta[rows[m], cols[m], :r] = np.concatenate(
                [g.delta for g, s in zip(gs, sel) if s])
        return pk

    # ------------------------------------------------------------------
    # extraction: kernel outputs -> Counters (one gather per wave)
    # ------------------------------------------------------------------
    def _fill_empty(self, chunk, out) -> None:
        overhead = self._comp.overhead_cycles
        for i in chunk:
            out[i] = Counters(float(overhead),
                              {p: 0 for p in self.uarch.ports})

    def _extract(self, pk: _ChunkPack, done, counts, out) -> None:
        """Batched Counters extraction: per-lane end times via one masked
        max + one scatter-max over final-writer rows, port counts via one
        ``tolist`` gather.  ``done`` is lane-major ``(E, S)`` (the numpy
        kernel hands in a transposed view)."""
        comp = self._comp
        E0, S0 = pk.E, pk.S
        core = (done[:E0, :S0] * pk.vis[:E0, :S0]).max(axis=1)
        fins = [(e, g.finals) for e, g in enumerate(pk.lane_progs)
                if g.finals.size]
        if fins:
            lanes = np.concatenate(
                [np.full(f.size, e, np.int64) for e, f in fins])
            rows = np.concatenate([f for _, f in fins])
            np.maximum.at(core, lanes, done[lanes, rows])
        overhead = comp.overhead_cycles
        ports = list(self.uarch.ports)
        perm = [comp.port_pos[p] for p in ports]
        cnt = counts[:E0][:, perm].tolist()
        for e, i in enumerate(pk.chunk):
            out[i] = Counters(float(int(core[e]) + overhead),
                              dict(zip(ports, cnt[e])))

    # ------------------------------------------------------------------
    # kernels
    # ------------------------------------------------------------------
    def _kernel_numpy(self, pk: _ChunkPack):
        comp = self._comp
        S, E = pk.S, pk.E
        issue, mask, lat, blk = pk.issue, pk.mask, pk.lat, pk.blk
        valid, prod, delta = pk.valid, pk.prod, pk.delta
        P = len(comp.ports)
        rows = np.arange(E)
        rows1 = rows[:, None]
        done = np.zeros((S, E), np.int32)
        port_free = np.zeros((E, P), np.int32)
        # dispatch tie-break key low bits: μop count (shifted) | port axis,
        # so one argmin realizes the scalar's (time, load, port) ordering.
        # Field widths are sized per chunk: the port axis needs
        # ``idx_bits``, counts are bounded by S, and time gets the rest.
        idx_bits = max((P - 1).bit_length(), 1)
        cnt_shift = (S << idx_bits).bit_length()
        pc_key = np.tile(np.arange(P, dtype=np.int64), (E, 1))
        big = np.iinfo(np.int64).max
        allowed = comp.mask_table[mask]                         # (S, E, P)
        prod_neg = prod < 0
        prod_c = np.maximum(prod, 0)
        vinc = valid.astype(np.int64) << idx_bits  # gated count increments
        # padding rows sit *after* each lane's real rows, so their (gated
        # out of the counts) dispatches cannot perturb any real result
        for j in range(S):
            val = np.where(prod_neg[j],
                           0, done[prod_c[j], rows1]) + delta[j]   # (E, R)
            ready = np.maximum(issue[j], val.max(axis=1))
            t = np.maximum(ready[:, None], port_free)
            key = np.where(allowed[j],
                           (t.astype(np.int64) << cnt_shift) + pc_key, big)
            best = key.argmin(axis=1)
            tmin = t[rows, best]
            done[j] = tmin + lat[j]
            port_free[rows, best] = tmin + blk[j]
            pc_key[rows, best] += vinc[j]
        return done, (pc_key >> idx_bits).astype(np.int32)

    # -- device backend (jax scan) -------------------------------------
    def _device_exec(self):
        """The lazy device executor (``set_devices`` drops it for
        rebuild on the new placement)."""
        from repro.core.device_mesh import resolve_devices  # noqa: PLC0415
        with self._host_lock:
            if self._device is None:
                self._device = _DeviceExec(
                    self._comp, devices=resolve_devices(self.devices),
                    min_lanes=self.min_lanes)
            return self._device

    def _run_device(self, batched, codes, progs, out, kernel_lock) -> None:
        """Pipelined, lane-sharded device execution: each chunk is split
        into per-core lane shards whose kernels run concurrently on the
        device pool (the kernels release the GIL), and chunk k+1 is packed
        on the host while chunk k executes (double-buffered bucket slots —
        a slot is only reused once its chunk's results have been
        *extracted*, not merely once its kernel finished: host buffers may
        be aliased zero-copy onto the device, and extraction still reads
        the slot's ``vis`` plane through the :class:`_ChunkPack` views).
        ``kernel_lock`` is held only around kernel dispatch, never around
        host packing or result waits."""
        from collections import deque  # noqa: PLC0415
        dev = self._device_exec()
        traced = obs.enabled()
        pending: deque = deque()
        jobs: list = []
        try:
            for c in batched:
                if max(progs[i].n_rows for i in c) == 0:
                    self._fill_empty(c, out)
                    continue
                if faults.active():
                    faults.check_wave("wave.kernel",
                                      [_fault_key(codes[i]) for i in c],
                                      backend=self.backend)
                jobs = []
                steps = slots = rows = 0
                with obs.span("wave.pack", lanes=len(c)) as psp:
                    if faults.active():
                        faults.check("wave.pack", backend=self.backend)
                    for sc in dev.shard(c, progs):
                        S0 = max(progs[i].n_rows for i in sc)
                        if S0 == 0:    # a shard of all-zero-μop programs
                            self._fill_empty(sc, out)
                            continue
                        R0 = max(max(progs[i].max_r for i in sc), 1)
                        slot = dev.acquire(S0, len(sc), R0)
                        pk = self._pack_chunk(sc, progs, bufs=slot.bufs)
                        jobs.append((pk, slot))
                        if traced:
                            # the bucket's serial steps, its lane-steps
                            # (padding included) and the real μop rows
                            E, S = slot.bufs[0].shape
                            steps += S
                            slots += E * S
                            rows += sum(progs[i].n_rows for i in sc)
                    psp.set(shards=len(jobs))
                if not jobs:
                    continue
                with obs.span("wave.dispatch", shards=len(jobs)) as dsp:
                    if traced:
                        dsp.set(steps=steps, slots=slots, rows=rows)
                    futs = dev.dispatch(jobs, kernel_lock)
                pending.append((jobs, futs))
                while len(pending) > 1:
                    self._finalize_device(*pending.popleft(), out)
            while pending:
                self._finalize_device(*pending.popleft(), out)
        except BaseException:
            # error path: slots must not stay leased forever (a transient
            # kernel failure would otherwise leak pinned buffers on every
            # wave).  The current chunk's slots have no dispatched
            # kernels if it never reached pending; dispatched chunks go
            # through _abort_jobs, which waits out in-flight kernels
            if not pending or pending[-1][0] is not jobs:
                for _, slot in jobs:
                    slot.release()
            while pending:
                _abort_jobs(*pending.popleft())
            raise

    def _finalize_device(self, jobs, futs, out) -> None:
        try:
            for (pk, slot), fut in zip(jobs, futs):
                # result_wait is kernel flight (device time the host spends
                # blocked on), extract is host gather work — trace them
                # apart so the report can tell device-bound from host-bound
                with obs.span("wave.result_wait", lanes=pk.E):
                    done, counts = fut.result()  # blocks until shard ends
                with obs.span("wave.extract", lanes=pk.E):
                    self._extract(pk, done, counts, out)
                # only now is the slot reusable: _extract read pk.vis,
                # which aliases the slot's vis buffer — releasing at
                # dispatch would let a fast same-bucket chunk k+1 re-zero
                # it mid-extraction
                slot.release()
        except BaseException:
            _abort_jobs(jobs, futs)
            raise


class _DeviceExec:
    """Per-machine device execution state: AOT-compiled kernels per shape
    bucket, the device-resident μop mask LUT, a small kernel thread pool
    (lane shards execute concurrently — the compiled kernels release the
    GIL), and recycled per-bucket packing-buffer slots whose lease lasts
    until their chunk's results are extracted (host buffers can be
    zero-copy aliases on device, and extraction reads the slot's ``vis``
    plane).

    With more than one resolved device the executor runs in **mesh
    mode**: each chunk's lanes are sharded across a 1-D ``lanes`` mesh
    (``shard_map`` over the bucketed kernel, lane-axis
    ``PartitionSpec``), with the chunk padded to a lanes-divisible bucket
    width so every device runs one equal lane block of the same
    executable.  Buffer slots are pooled per ``(bucket, mesh width)`` —
    the per-device pools of the lease protocol — and kernel dispatch is
    serialized by the executor's **per-device-subset lock** (see
    :func:`repro.core.device_mesh.dispatch_lock`) instead of the
    campaign-wide execute lock, so machines placed on disjoint device
    subsets never serialize each other's kernels."""

    _BUCKETS_MAX = 8     # bucket slot-ring pool bound (LRU)
    _SHARD_MIN_LANES = 64

    def __init__(self, comp: CompiledUArch, devices=(),
                 min_lanes: int = DEFAULT_MIN_LANES):
        from repro.core.device_mesh import (  # noqa: PLC0415
            dispatch_lock, jax_devices)
        self.comp = comp
        self.devices = tuple(devices)
        self.min_lanes = max(min_lanes, 1)
        all_devs = jax_devices()
        default = all_devs[0] if all_devs else None
        # mesh mode whenever the placement is not simply "the default
        # device": >1 device shards lanes; a single non-default device
        # (campaign placement with more machines than devices) still needs
        # the mesh wrapper to pin its kernels to that device
        self.mesh_mode = bool(self.devices) and (
            len(self.devices) > 1
            or (default is not None and self.devices[0].id != default.id))
        self.n_mesh = len(self.devices) if self.mesh_mode else 1
        self.lut = None if self.mesh_mode else comp.device_mask_table()
        self._luts: dict = {}    # mesh width -> replicated device LUT
        # per-subset dispatch lock (module-wide): machines sharing this
        # device subset serialize host-side dispatch on it; disjoint
        # subsets dispatch concurrently
        self.dispatch_lock = dispatch_lock(
            self.devices or ((default,) if default is not None else ()))
        self.compiles = 0
        self.kernel_calls = 0
        self.buckets: set = set()
        # per-device telemetry: device id -> counters (a mesh dispatch
        # counts on every participating device)
        self.per_device: dict = {
            d.id: {"compiles": 0, "kernel_calls": 0, "lanes": 0,
                   "buckets": set()}
            for d in (self.devices or ((default,) if default else ()))}
        self.n_workers = max(1, os.cpu_count() or 1)
        self._pool = None
        self._lock = threading.Lock()   # guards slot leasing / ring LRU
        self._rings: dict = {}   # bucket -> slot list (LRU by bucket)

    def stats(self) -> dict:
        return {"backend": "jax", "compiles": self.compiles,
                "kernel_calls": self.kernel_calls,
                "buckets": sorted(self.buckets),
                "mesh": self.mesh_mode,
                "devices": [d.id for d in self.devices],
                "per_device": {
                    did: {"compiles": c["compiles"],
                          "kernel_calls": c["kernel_calls"],
                          "lanes": c["lanes"],
                          "buckets": sorted(c["buckets"])}
                    for did, c in self.per_device.items()}}

    # -- lane sharding --------------------------------------------------
    def shard(self, chunk, progs) -> list:
        """Split a chunk into contiguous per-core lane shards (the chunk
        arrives sorted by descending length, so later shards pad to a
        smaller S bucket).  In mesh mode the chunk stays whole: per-device
        subdivision happens through the lane-axis sharding of one fused
        kernel, not through separate host-dispatched shards."""
        if self.mesh_mode:
            return [chunk]
        E0 = len(chunk)
        n = min(self.n_workers, E0 // self._SHARD_MIN_LANES)
        if n <= 1:
            return [chunk]
        per = (E0 + n - 1) // n
        return [chunk[k:k + per] for k in range(0, E0, per)]

    def mesh_width(self, E0: int) -> int:
        """Devices used for an ``E0``-lane chunk: capped so every
        per-device lane shard keeps at least ``min_lanes`` lanes — the
        thin-chunk scalar crossover applies to the *per-device shard
        width*, not the whole wave (a wave wide enough in total but thin
        per device runs on fewer devices instead of paying kernel
        overhead on sub-crossover shards)."""
        return max(1, min(self.n_mesh, E0 // self.min_lanes))

    # -- buckets / buffer slots ----------------------------------------
    @staticmethod
    def bucket_shape(S0: int, E0: int, R0: int) -> tuple:
        return (_bucket(S0, 32), _bucket(E0, 8), _next_pow2(R0))

    def _mesh_bucket(self, S0: int, E0: int, R0: int, n_use: int) -> tuple:
        """Mesh-mode bucket: lane width padded per device and multiplied
        back up, so the global width is lanes-divisible (every device gets
        one equal ``E_dev`` block of the same bucketed executable)."""
        e_dev = _bucket((E0 + n_use - 1) // n_use, 8)
        return (_bucket(S0, 32), e_dev * n_use, _next_pow2(R0))

    def acquire(self, S0: int, E0: int, R0: int) -> "_BufSlot":
        """Lease a packing-buffer slot for one shard.  A slot stays leased
        from here until :meth:`~_BufSlot.release` in ``_finalize_device``
        — through packing, kernel flight, AND extraction (two shards of
        one chunk often share a bucket and must never share buffers; the
        kernel may read the buffers as zero-copy device aliases; and
        extraction still reads the slot's ``vis`` plane).  If every slot
        is leased a new one is allocated: live slots are bounded by the
        lease discipline itself (pipeline depth x shards per chunk), so
        the ring never grows past warm steady state.  Mutex-guarded so
        concurrent ``run_batch`` callers can never double-lease a slot.

        In mesh mode the slot pool is keyed by ``(bucket, mesh width)`` —
        per-device buffer pools: a slot's buffers are sharded onto the
        first ``n_use`` devices at dispatch, so slots of different mesh
        widths never alias and a reused slot always re-shards onto the
        same device subset."""
        if self.mesh_mode:
            n_use = self.mesh_width(E0)
            shape = self._mesh_bucket(S0, E0, R0, n_use)
            key = shape + (n_use,)
        else:
            n_use = None
            shape = self.bucket_shape(S0, E0, R0)
            key = shape
        with self._lock:
            ring = self._rings.get(key)
            if ring is None:
                while len(self._rings) >= self._BUCKETS_MAX:
                    self._rings.pop(next(iter(self._rings)))
                ring = self._rings[key] = []
            else:
                self._rings[key] = self._rings.pop(key)   # LRU touch
            for slot in ring:   # a released slot has been fully extracted
                if not slot.leased:
                    slot.leased = True
                    return slot
            slot = _BufSlot(self._alloc(*shape), n_use)
            ring.append(slot)
            slot.leased = True
            return slot

    @staticmethod
    def _alloc(S, E, R):
        return (np.zeros((E, S), np.int32), np.zeros((E, S), np.int32),
                np.zeros((E, S), np.int32), np.zeros((E, S), np.int32),
                np.zeros((E, S), bool), np.full((E, S, R), -1, np.int32),
                np.zeros((E, S, R), np.int32), np.zeros((E, S), np.int32))

    # -- dispatch -------------------------------------------------------
    def _get_pool(self):
        with self._lock:   # concurrent callers must not each build a pool
            if self._pool is None:
                from concurrent.futures import (  # noqa: PLC0415
                    ThreadPoolExecutor)
                self._pool = ThreadPoolExecutor(
                    max_workers=self.n_workers,
                    thread_name_prefix="batch-sim-kernel")
            return self._pool

    def _mesh_lut(self, n_use: int):
        """The μop port-mask LUT replicated across the first ``n_use``
        mesh devices (resident per mesh width, transferred once)."""
        lut = self._luts.get(n_use)
        if lut is None:
            import jax  # noqa: PLC0415
            from repro.core.device_mesh import lane_mesh  # noqa: PLC0415
            mesh = lane_mesh(self.devices[:n_use])
            lut = jax.device_put(self.comp.mask_table, mesh.replicated)
            self._luts[n_use] = lut
        return lut

    def _record(self, devs, bucket, compiled_now, E0, e_dev) -> None:
        """Per-device telemetry for one dispatch: every participating
        device counts the call; real (non-padding) lanes are attributed
        by their contiguous block position."""
        for k, d in enumerate(devs):
            c = self.per_device.setdefault(
                d.id, {"compiles": 0, "kernel_calls": 0, "lanes": 0,
                       "buckets": set()})
            c["kernel_calls"] += 1
            c["compiles"] += 1 if compiled_now else 0
            c["buckets"].add(bucket)
            c["lanes"] += max(0, min(E0 - k * e_dev, e_dev))

    def dispatch(self, jobs, kernel_lock=None) -> list:
        """Enqueue one kernel call per shard on the device pool; returns
        one future per job yielding host ``(done, counts)`` arrays.
        Dispatch is guarded by the executor's per-device-subset lock —
        NOT the campaign-wide ``kernel_lock`` (accepted for protocol
        compatibility, unused here): only the enqueue is host-side Python,
        execution parallelism is the pool's and the devices' (compiled
        kernels release the GIL), and machines placed on disjoint device
        subsets must never serialize each other's kernels."""
        if faults.active():
            faults.check("device.dispatch", backend="jax")
        pool = self._get_pool()
        M, P = self.comp.mask_table.shape
        calls = []
        for pk, slot in jobs:
            E, S = pk.issue.shape
            R = pk.prod.shape[2]
            if slot.n_use is not None:          # mesh-mode shard
                from repro.core.device_mesh import (  # noqa: PLC0415
                    lane_mesh)
                n_use = slot.n_use
                e_dev = E // n_use
                mesh = lane_mesh(self.devices[:n_use])
                fn, compiled_now = _compiled_kernel(S, e_dev, R, M, P,
                                                    mesh=mesh)
                lut = self._mesh_lut(n_use)
                devs = mesh.devices
                self._record(devs, (S, e_dev, R), compiled_now,
                             pk.E, e_dev)
            else:
                n_use, e_dev = 1, E
                fn, compiled_now = _compiled_kernel(S, E, R, M, P)
                lut = self.lut
                devs = self.devices[:1]
                self._record(devs, (S, E, R), compiled_now, pk.E, E)
            if compiled_now:
                self.compiles += 1
            self.buckets.add((S, E, R))
            self.kernel_calls += 1
            calls.append((fn, (pk.issue, pk.mask, pk.lat, pk.blk, pk.valid,
                               pk.prod, pk.delta, lut)))
        with obs.wait_lock(self.dispatch_lock, "wave.dispatch_lock_wait"):
            futs = [pool.submit(_run_kernel, fn, args) for fn, args in calls]
        # the slots stay leased: ``_finalize_device`` releases them only
        # after extraction, which reads the slots' vis buffers
        return futs


class _BufSlot:
    """One recycled packing-buffer set.  ``leased`` is True from
    ``_DeviceExec.acquire`` until :meth:`release` after the chunk's
    results are *extracted* — kernel completion alone does not free the
    slot, because extraction reads the slot's ``vis`` plane through the
    :class:`_ChunkPack` views (and the kernel may have read the buffers
    as zero-copy device aliases).  ``n_use`` records the mesh width the
    slot was bucketed for (``None`` on the single-device path): the
    dispatcher shards the slot's buffers across exactly that many
    devices, so slots are effectively pooled per device subset."""
    __slots__ = ("bufs", "leased", "n_use")

    def __init__(self, bufs, n_use=None):
        self.bufs = bufs
        self.leased = False
        self.n_use = n_use

    def release(self) -> None:
        self.leased = False


def _abort_jobs(jobs, futs) -> None:
    """Error-path slot cleanup: wait for every dispatched shard kernel to
    settle (a still-running kernel may be reading the slot's buffers,
    possibly as zero-copy device aliases) and release every slot —
    idempotent, so jobs already released by the success path are fine."""
    for (_, slot), fut in zip(jobs, futs):
        try:
            fut.exception()          # blocks until the kernel settles
        except BaseException:        # cancelled: the kernel never ran
            pass
        slot.release()


def _run_kernel(fn, args):
    """Pool worker: execute one compiled shard kernel and realize its
    outputs on the host (so finalization only touches host arrays; the
    packing buffers themselves stay leased until extraction).  Its device
    time is in the profiler trace, under the program's name."""
    done, counts = fn(*args)
    return np.asarray(done), np.asarray(counts)


# ---------------------------------------------------------------------------
# compiled device kernels (module-wide: shared across machines per shape)
# ---------------------------------------------------------------------------

_JAX = ()


def _jax():
    global _JAX
    if _JAX == ():
        try:
            import jax  # noqa: F401
            _JAX = jax
        except ImportError:
            _JAX = None
    return _JAX


_EXEC_CACHE: dict = {}
_EXEC_CACHE_MAX = 128
_EXEC_LOCK = threading.Lock()


def _compiled_kernel(S: int, E: int, R: int, M: int, P: int, mesh=None):
    """AOT-compiled dispatch kernel for one shape bucket.  Returns
    ``(callable, compiled_now)``; the executable cache is module-wide, so
    machines sharing bucket shapes share compilations — and a module lock
    keeps concurrent campaign workers from paying for the same multi-
    second XLA compile twice.

    With ``mesh`` (a :class:`~repro.core.device_mesh.LaneMesh`) the
    bucketed kernel is wrapped in ``shard_map`` over the mesh's ``lanes``
    axis: ``E`` is then the *per-device* lane width and the executable
    takes ``(E * mesh.n, S)``-shaped operands whose lane blocks land one
    per device.  Executables are device-bound, so the mesh's device-id
    tuple is part of the cache key."""
    key = (S, E, R, M, P) + ((mesh.key,) if mesh is not None else ())
    hit = _EXEC_CACHE.get(key)
    if hit is not None:
        return hit, False
    with _EXEC_LOCK:
        hit = _EXEC_CACHE.get(key)      # double-check under the lock
        if hit is not None:
            return hit, False
        from repro.core.device_mesh import (  # noqa: PLC0415
            enable_compile_cache)
        enable_compile_cache()
        with obs.span("wave.compile", backend="jax", bucket=list(key[:5])):
            return _compile_kernel(key, mesh), True


def lower_kernel(S: int, E: int, R: int, M: int, P: int, mesh=None,
                 sharding=None):
    """Lower the dispatch kernel for one shape bucket (``jax.stages.
    Lowered``; ``.compile()`` it).  Without ``mesh`` the operands are
    placed by ``sharding`` (default: jax's default device) — pass a
    sharding on a described TPU device to compile for a chip that is not
    attached.  With ``mesh`` the kernel is wrapped in ``shard_map`` over
    the mesh's ``lanes`` axis and ``E`` is the per-device lane width."""
    jax = _jax()
    import jax.numpy as jnp

    if mesh is not None:
        device = mesh.devices[0]
    elif sharding is not None:
        device = next(iter(sharding.device_set))
    else:
        device = jax.devices()[0]
    fn = _build_scan_fn()
    sd = jax.ShapeDtypeStruct
    if mesh is None:
        s2 = s3 = rep = sharding
        Eg = E
    else:
        # the per-shard fn sees (E, S) blocks; lanes are independent, so
        # no collectives and no replication to check
        fn = jax.shard_map(
            fn, mesh=mesh.mesh,
            in_specs=(mesh.spec2,) * 5 + (mesh.spec3,) * 2
            + (mesh.repl_spec,),
            out_specs=(mesh.spec2, mesh.spec2), check_vma=False)
        s2, s3, rep = mesh.shard2, mesh.shard3, mesh.replicated
        Eg = E * mesh.n
    shapes = (sd((Eg, S), jnp.int32, sharding=s2),
              sd((Eg, S), jnp.int32, sharding=s2),
              sd((Eg, S), jnp.int32, sharding=s2),
              sd((Eg, S), jnp.int32, sharding=s2),
              sd((Eg, S), jnp.bool_, sharding=s2),
              sd((Eg, S, R), jnp.int32, sharding=s3),
              sd((Eg, S, R), jnp.int32, sharding=s3),
              sd((M, P), jnp.bool_, sharding=rep))
    # donating the issue plane lets XLA write the (E, S) ``done`` output
    # into it (the only input an output can alias); the CPU backend does
    # not implement donation, so gate on the target device's platform
    donate = (0,) if device.platform != "cpu" else ()
    return jax.jit(fn, donate_argnums=donate).lower(*shapes)


def _compile_kernel(key, mesh=None):
    compiled = lower_kernel(*key[:5], mesh=mesh).compile()
    while len(_EXEC_CACHE) >= _EXEC_CACHE_MAX:
        _EXEC_CACHE.pop(next(iter(_EXEC_CACHE)))
    _EXEC_CACHE[key] = compiled
    return compiled


def _scan_block(S: int) -> int:
    """Inner scan-block length: the history buffer is updated once per
    block (one contiguous ``(K, E)`` write), so the per-step loop carries
    only a small block of ``done`` values — carrying (and copying) the
    whole ``(S, E)`` history every step is what made the naive scan lose
    to the numpy kernel.  Shape buckets are ``32*2^k`` or ``48*2^k``, so
    one of these always divides S exactly."""
    for k in (32, 48, 16, 8, 4, 2):
        if S % k == 0:
            return k
    return 1


def _build_scan_fn():
    """The ``lax.scan`` dispatch kernel: one step per μop position, all
    experiment lanes advancing in lockstep.  Two-level structure: an outer
    scan over blocks of K μop positions gathers every finished-block
    producer value in one pass and writes the block's ``done`` values back
    to the history with one contiguous update; the inner scan resolves
    intra-block producers from its small ``(K, E)`` carry.  The dispatch
    tie-break is the two-pass min (earliest time, then least load, then
    lowest port index on the *sorted* port axis) — pinned equivalent to
    the numpy kernel's packed-key argmin by the tie-break differential
    tests."""
    import jax.numpy as jnp
    from jax import lax

    def run(issue_l, mask_l, lat_l, blk_l, valid_l, prod_l, delta_l, lut):
        # inputs arrive lane-major (E, S) — the host packs each lane's
        # rows contiguously; one on-device transpose beats a strided
        # host scatter into row-major buffers
        issue = issue_l.T
        mask_id = mask_l.T
        lat = lat_l.T
        blk = blk_l.T
        valid = valid_l.T
        prod = prod_l.transpose(1, 0, 2)
        delta = delta_l.transpose(1, 0, 2)
        S, E = issue.shape
        R = prod.shape[2]
        K = _scan_block(S)
        nb = S // K
        # disallowed-port sentinel: INT32_MAX, matching the numpy kernel's
        # int64-max (real candidate times stay below it over the whole
        # documented cycles < 2^31 - 1 envelope; count keys are far
        # smaller), so a disallowed port can never win either min pass
        big = jnp.int32(2**31 - 1)
        P = lut.shape[1]
        # the (count << idx_bits | port) dispatch key: one int32 per port,
        # so the tie-break needs a single min+argmin pass (the numpy
        # kernel's packed-key ordering, realized in two int32 fields)
        idx_bits = max((P - 1).bit_length(), 1)
        pcp0 = jnp.arange(P, dtype=jnp.int32)
        lanes = jnp.arange(E, dtype=jnp.int32)
        # per-μop allowed-port rows, expanded once outside the loop (one
        # vectorized LUT gather instead of one per step)
        allowed = lut[mask_id]                                  # (S,E,P)
        # producer indices flattened for one-gather resolution: in-block
        # rows resolve against the running block, finished rows against
        # the history — both masks precomputed for the whole program
        in_block = prod >= (jnp.arange(S, dtype=jnp.int32)
                            // K * K)[:, None, None]            # (S,E,R)
        rel_flat = (jnp.clip(prod % jnp.int32(K), 0, K - 1) * E
                    + lanes[None, :, None])                     # (S,E,R)
        hist_flat = jnp.clip(prod, 0, S - 1) * E + lanes[None, :, None]
        prod_neg = prod < 0

        def per_block(a):
            return a.reshape((nb, K) + a.shape[1:])

        def block(carry, xsb):
            hist, pf, pcp = carry           # (S,E), (E,P), (E,P)
            b, isu, la, bl, va, de, alw, inb_m, relf, histf, png = xsb
            # producers in finished blocks: one gather for the whole block
            old = jnp.where(png, 0, jnp.take(hist.reshape(-1), histf))

            def step(icarry, xs):
                bdone, pf, pcp = icarry     # (K,E), (E,P), (E,P)
                (j, isuj, laj, blj, vaj, dej, alwj, inbj, relj,
                 oldj) = xs
                inb = jnp.take(bdone.reshape(-1), relj)      # (E,R)
                val = jnp.where(inbj, inb, oldj) + dej
                ready = jnp.maximum(isuj, val.max(axis=1))
                t = jnp.maximum(ready[:, None], pf)
                ta = jnp.where(alwj, t, big)
                tmin = ta.min(axis=1)
                key = jnp.where(ta == tmin[:, None], pcp, big)
                best = jnp.argmin(key, axis=1)
                hit = (pcp0[None, :] == best[:, None]) & vaj[:, None]
                bdone = lax.dynamic_update_slice(
                    bdone, jnp.where(vaj, tmin + laj, 0)[None, :], (j, 0))
                pf = jnp.where(hit, (tmin + blj)[:, None], pf)
                pcp = pcp + (hit.astype(jnp.int32) << idx_bits)
                return (bdone, pf, pcp), None

            ixs = (jnp.arange(K), isu, la, bl, va, de, alw, inb_m, relf,
                   old)
            (bdone, pf, pcp), _ = lax.scan(
                step, (jnp.zeros((K, E), jnp.int32), pf, pcp), ixs)
            hist = lax.dynamic_update_slice(hist, bdone, (b * K, 0))
            return (hist, pf, pcp), None

        xs = (jnp.arange(nb), per_block(issue), per_block(lat),
              per_block(blk), per_block(valid), per_block(delta),
              per_block(allowed), per_block(in_block),
              per_block(rel_flat), per_block(hist_flat),
              per_block(prod_neg))
        carry = (jnp.zeros((S, E), jnp.int32),
                 jnp.zeros((E, P), jnp.int32),
                 jnp.tile(pcp0, (E, 1)))
        (hist, _, pcp), _ = lax.scan(block, carry, xs)
        return hist.T, pcp >> idx_bits

    return run

