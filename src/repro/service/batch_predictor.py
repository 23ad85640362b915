"""Vectorized batch prediction: many basic blocks, one array pass.

``core/predictor.py`` is the single-block *reference*; this module is the
throughput path behind the service. It packs every block's summed port-usage
row into one dense ``(blocks × combos)`` matrix and computes all port bounds
with a single matrix product against the model's precomputed min-cut
candidate sets (the closed form in ``core/lp.py``), instead of solving one
LP per block. Front-end bounds, per-port pressure, the latency bound, and
the bottleneck tie-break reuse the reference helpers, so the results are
bit-identical to calling :func:`repro.core.predictor.predict` per block:

* port-usage μop counts are integers (PortUsage / the XML schema), so the
  matrix product's float64 sums are exact regardless of summation order;
* the min-cut maximum over the model-wide candidate closure equals the
  maximum over each block's own closure (shrinking a candidate to the union
  of the combinations it contains only increases its ratio);
* blocks with more distinct combinations than ``CUT_COMBO_CAP`` fall back
  to the same LP on the same insertion-ordered usage dict as the reference.

Two vectorized backends share the same integer cut matrices
(:func:`repro.core.lp.cut_matrices`): a numpy matmul (always available) and
a device-resident jax kernel for wide waves — usage rows ship to the device
as one int32 array, ``demand = u @ mask`` runs as an integer matrix product
against the device-resident candidate masks, and the winning candidate per
block is selected with an exact integer cross-multiplication reduce; only
the final ``demand/size`` division happens in float64 on the host, so the
device path is bit-identical to the scalar reference (equal rational ratios
round to the same double). Wave shapes are bucketed with the same
quarter-octave rule as ``core/batch_sim.py`` and each bucket's kernel is
AOT-compiled once.
"""
from __future__ import annotations

import os
import threading

import numpy as np

from repro.obs import tracer as obs
from repro.core.characterize import PerfModel
from repro.core.isa import ISA
from repro.core.lp import (CUT_COMBO_CAP, cut_matrices, port_bound_from_usage,
                           union_closure)
from repro.core.predictor import (Prediction, UnknownInstructionError,
                                  _latency_bound, check_block,
                                  classify_bottleneck, port_pressure,
                                  sum_usage)

# below this many closed-form rows the host↔device round trip costs more
# than the numpy matmul saves; tuned on the bulk-wave benchmark
MIN_DEVICE_BLOCKS = 32


class BatchPredictor:
    """Precompiled predictor for one :class:`PerfModel`.

    With a ``machine`` attached (a simulated core or its measurement
    engine), the predictor also offers a *simulate-backed* mode:
    :meth:`simulate_batch` measures whole block waves on the machine —
    batched through its ``run_batch`` backend — giving the ground truth
    the analytic bounds can be judged against at workload scale."""

    def __init__(self, model: PerfModel, isa: ISA, issue_width: int = 4,
                 machine=None, *, backend: str | None = None,
                 min_device_blocks: int | None = None):
        self.model = model
        self.isa = isa
        self.issue_width = issue_width
        self.machine = machine
        # distinct port combinations across the model, in a fixed order
        combos: list[frozenset] = []
        index: dict[frozenset, int] = {}
        for im in model.instructions.values():
            if im.port_usage:
                for pc in im.port_usage.usage:
                    if pc not in index:
                        index[pc] = len(combos)
                        combos.append(pc)
        self._combos = combos
        self._combo_idx = index
        # model-wide min-cut candidates: all unions of the model's combos.
        # None => too many to enumerate; per-block closed form / LP instead.
        cand = union_closure(combos) if combos else []
        if cand:
            mask_i, size_i = cut_matrices(combos, cand)
            self._mask_i = mask_i                       # C×S int32
            self._size_i = size_i                       # S   int32
            self._cut_mask = mask_i.astype(float)       # C×S
            self._cut_size = size_i.astype(float)
        else:
            self._mask_i = self._size_i = None
            self._cut_mask = None
            self._cut_size = None
        # canonical port table (binary wire + device kernels index into it)
        self.port_names = sorted({p for pc in combos for p in pc})
        self.port_index = {p: i for i, p in enumerate(self.port_names)}
        # vectorized closed-form backend: "numpy" | "jax" | "auto"/None
        if backend is None:
            backend = os.environ.get("REPRO_PREDICT_BACKEND", "auto")
        if backend == "auto":
            try:
                import jax  # noqa: F401, PLC0415
                backend = "jax"
            except Exception:
                backend = "numpy"
        if backend not in ("numpy", "jax"):
            raise ValueError(f"unknown predict backend {backend!r}")
        self.backend = backend
        self.min_device_blocks = (MIN_DEVICE_BLOCKS if min_device_blocks
                                  is None else min_device_blocks)
        self._dev_lock = threading.Lock()
        self._dev_kernels: dict[int, object] = {}   # bucket size -> compiled
        self._dev_mask = None                       # device-resident C×S
        self._stats = {"numpy_waves": 0, "device_waves": 0,
                       "device_blocks": 0, "device_compiles": 0,
                       "device_fallbacks": 0}

    # ------------------------------------------------------------------
    def predict(self, code) -> Prediction:
        return self.predict_batch([code])[0]

    def simulate_batch(self, blocks, kernel_lock=None,
                       devices=None) -> list[float]:
        """Measured steady-state cycles per block iteration, for a whole
        wave of blocks at once (Algorithm-2 differencing on the attached
        machine; the engine dedups the wave and executes the miss-set
        through the machine's batched backend — device-resident when the
        machine's backend is ``jax``, with warm waves skipping
        lowering via the machine's lowering cache).  ``kernel_lock``
        serializes GIL-bound kernel execution against other engines
        sharing the lock; host lowering/packing stays concurrent.

        ``devices`` (an integer count, ``"all"``, or an explicit jax
        device sequence) re-places the machine's wave execution before
        this wave — with more than one device the wave's lanes shard
        across a 1-D mesh (see :mod:`repro.core.device_mesh`), falling
        back gracefully to the single-device path when the host has fewer
        devices; results are bit-identical at every device count.
        ``None`` keeps the machine's current placement (the
        ``REPRO_SIM_DEVICES`` default)."""
        if self.machine is None:
            raise ValueError("simulate-backed mode needs a machine "
                             "(BatchPredictor(..., machine=...))")
        from repro.core.engine import Experiment, as_engine  # noqa: PLC0415

        blocks = list(blocks)
        # the span inherits the serving request's trace_id when called
        # from a traced server thread (see repro.obs.tracer)
        with obs.span("predict.simulate", blocks=len(blocks)):
            if devices is not None:
                setter = getattr(self.machine, "set_devices", None)
                if setter is not None:
                    setter(devices)
            engine = as_engine(self.machine)
            res = engine.submit([Experiment.of(b) for b in blocks],
                                kernel_lock=kernel_lock)
            return [c.cycles for c in res]

    def predict_batch(self, blocks, on_error: str = "raise") -> list:
        """Predictions for many blocks in one pass.

        ``on_error="raise"`` raises :class:`UnknownInstructionError` for the
        first block referencing uncharacterized instructions;
        ``on_error="return"`` yields the exception object in that block's
        slot instead (the service's per-request structured errors).

        Traced as a ``predict.batch`` span (inheriting the serving
        request's ``trace_id`` when reached from a traced server
        thread)."""
        codes = [list(b) for b in blocks]
        with obs.span("predict.batch", blocks=len(codes)):
            return self._predict_batch(codes, on_error)

    def _predict_batch(self, codes, on_error: str) -> list:
        errors: dict[int, UnknownInstructionError] = {}
        for i, code in enumerate(codes):
            try:
                check_block(self.model, code, self.isa)
            except UnknownInstructionError as e:
                if on_error == "raise":
                    raise
                errors[i] = e
        valid = [i for i in range(len(codes)) if i not in errors]
        # summed usage per block, in code order (reference semantics)
        sums = {i: sum_usage(self.model, codes[i]) for i in valid}
        port_bounds = self._port_bounds(sums)
        out: list = [None] * len(codes)
        for i in valid:
            usage_sum, uops = sums[i]
            fe = uops / self.issue_width
            lat = _latency_bound(self.model, self.isa, codes[i])
            pb = port_bounds[i]
            cycles = max(pb, lat, fe)
            out[i] = Prediction(cycles, pb, lat, fe,
                                port_pressure(usage_sum),
                                classify_bottleneck(cycles, pb, lat))
        for i, e in errors.items():
            out[i] = e
        return out

    # ------------------------------------------------------------------
    def _port_bounds(self, sums: dict) -> dict:
        """Port bound per block index: one matrix pass over the dense usage
        rows where the closed form applies, LP fallback elsewhere."""
        bounds: dict[int, float] = {}
        if not sums:
            return bounds
        idxs = sorted(sums)
        fast_rows: list[int] = []
        for i in idxs:
            usage_sum, _ = sums[i]
            distinct = sum(1 for n in usage_sum.values() if n > 0)
            if distinct == 0:
                bounds[i] = 0.0
            elif distinct > CUT_COMBO_CAP or self._cut_mask is None:
                # same rule + same insertion-ordered dict as the reference
                bounds[i] = port_bound_from_usage(usage_sum)
            else:
                fast_rows.append(i)
        if fast_rows:
            best = None
            if (self.backend == "jax"
                    and len(fast_rows) >= self.min_device_blocks):
                best = self._device_bounds(sums, fast_rows)
            if best is None:
                self._stats["numpy_waves"] += 1
                u = np.zeros((len(fast_rows), len(self._combos)))
                for r, i in enumerate(fast_rows):
                    for pc, n in sums[i][0].items():
                        u[r, self._combo_idx[pc]] = n
                demand = u @ self._cut_mask          # rows × candidates
                ratios = demand / self._cut_size
                best = ratios.max(axis=1)
            for r, i in enumerate(fast_rows):
                bounds[i] = float(best[r])
        return bounds

    # ------------------------------------------------------------------
    # device-resident closed form (jax backend)
    # ------------------------------------------------------------------
    def _device_bounds(self, sums: dict, fast_rows: list):
        """All fast-row port bounds in one device call, or None to fall
        back to numpy (non-integer counts, overflow risk, jax trouble).

        The kernel is exact: int32 ``demand = u @ mask`` then an integer
        cross-multiplication argmax over candidates; the single float64
        division happens host-side, so results are bit-identical to the
        scalar reference."""
        n = len(fast_rows)
        u = np.zeros((n, len(self._combos)), dtype=np.int32)
        for r, i in enumerate(fast_rows):
            for pc, cnt in sums[i][0].items():
                v = int(cnt)
                if v != cnt:                    # non-integer μop count
                    self._stats["device_fallbacks"] += 1
                    return None
                u[r, self._combo_idx[pc]] = v
        # cross products stay well inside int32: demand ≤ row total
        if int(u.sum(axis=1).max()) * int(self._size_i.max()) >= 2 ** 31:
            self._stats["device_fallbacks"] += 1
            return None
        try:
            num, den = self._device_call(u)
        except Exception:
            self._stats["device_fallbacks"] += 1
            return None
        self._stats["device_waves"] += 1
        self._stats["device_blocks"] += n
        return num[:n].astype(np.float64) / den[:n].astype(np.float64)

    def _device_call(self, u: np.ndarray):
        from repro.core.batch_sim import _bucket  # noqa: PLC0415

        bucket = _bucket(u.shape[0], 8)
        fn = self._dev_kernels.get(bucket)
        if fn is None:
            fn = self._compile_kernel(bucket)
        if u.shape[0] != bucket:
            u = np.concatenate(
                [u, np.zeros((bucket - u.shape[0], u.shape[1]), u.dtype)])
        num, den = fn(u, self._dev_mask, self._dev_size)
        return np.asarray(num), np.asarray(den)

    def _compile_kernel(self, bucket: int):
        """AOT-compile the port-bound kernel for one shape bucket (same
        quarter-octave buckets as ``core/batch_sim``); the candidate mask
        and sizes live on the device across calls."""
        import jax  # noqa: PLC0415
        import jax.numpy as jnp  # noqa: PLC0415
        from repro.core.device_mesh import (  # noqa: PLC0415
            enable_compile_cache)

        with self._dev_lock:
            fn = self._dev_kernels.get(bucket)
            if fn is not None:
                return fn
            if self._dev_mask is None:
                self._dev_mask = jax.device_put(
                    jnp.asarray(self._mask_i, dtype=jnp.int32))
                self._dev_size = jax.device_put(
                    jnp.asarray(self._size_i, dtype=jnp.int32))
            enable_compile_cache()
            with obs.span("predict.compile", bucket=bucket):
                fn = lower_port_bound(bucket, *self._mask_i.shape).compile()
            self._stats["device_compiles"] += 1
            self._dev_kernels[bucket] = fn
            return fn

    def backend_stats(self) -> dict:
        """Counters for the vectorized closed-form backend (wave counts,
        device compiles/fallbacks) — absorbed into service metrics."""
        return {"backend": self.backend, **self._stats}


def port_bound_kernel(u, mask, size):
    """Per block row of ``u`` (B×C int32 μop counts per port combination):
    the winning min-cut candidate's ``(demand, size)`` over ``mask`` (C×S
    candidate masks) and ``size`` (S candidate port counts).  Exact:
    ``demand = u @ mask`` in int32, then an integer cross-multiplication
    argmax over candidates."""
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    demand = u @ mask                           # B×S int32, exact
    den = jnp.broadcast_to(size, demand.shape)

    def pick(acc, x):
        an, ad = acc
        bn, bd = x
        take = an * bd < bn * ad                # exact ratio compare
        return (jnp.where(take, bn, an), jnp.where(take, bd, ad))

    return jax.lax.reduce((demand, den), (jnp.int32(0), jnp.int32(1)),
                          pick, (1,))


def lower_port_bound(rows: int, combos: int, candidates: int,
                     sharding=None):
    """Lower :func:`port_bound_kernel` for a ``rows``-block bucket
    (``jax.stages.Lowered``; ``.compile()`` it).  ``sharding`` places the
    operands (default: jax's default device) — a sharding on a described
    TPU device compiles for a chip that is not attached."""
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415

    sd = jax.ShapeDtypeStruct
    return jax.jit(port_bound_kernel).lower(
        sd((rows, combos), jnp.int32, sharding=sharding),
        sd((combos, candidates), jnp.int32, sharding=sharding),
        sd((candidates,), jnp.int32, sharding=sharding))
