"""Lane-axis device mesh: the substrate for multi-device wave execution.

Characterization campaigns are embarrassingly parallel — thousands of
independent microbenchmark lanes per wave — so the natural multi-device
decomposition is a **1-D mesh over a single ``lanes`` axis**: every device
runs the same bucketed dispatch kernel on its own block of experiment
lanes (``shard_map`` with a lane-axis ``PartitionSpec``; the kernel has no
cross-lane communication, so the partitions are fully independent SPMD).

This module owns the pieces of that substrate that are *not* kernel code:

* **Device resolution** — :func:`resolve_devices` turns a user-facing
  spec (``devices=`` constructor argument or the ``REPRO_SIM_DEVICES``
  environment variable: an integer count, ``"all"``, or an explicit
  device sequence) into an ordered tuple of jax devices, clamped to what
  the host actually has.  Real accelerators appear here on real hardware
  (``python chip_smoke.py --chips 4`` drives the mesh on a four-chip TPU
  host); CPU CI forces host devices via
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (which must be
  set *before* jax is first imported — hence the subprocess pattern in
  ``tests/test_multidevice.py`` and ``bench_device_scaling``, which is
  CPU-only: a child process cannot take a chip its parent holds).  With
  jax missing or a single device the resolution degrades gracefully and
  wave execution stays on the single-device path, bit-identical.

* **Platform and compile cache** — :func:`device_platform` is what the
  wave backend and the compile cache are chosen from (never a user
  knob); :func:`enable_compile_cache` turns JAX's persistent compilation
  cache on before the first kernel compile on a TPU.

* **Mesh construction** — :class:`LaneMesh` wraps a
  ``jax.sharding.Mesh`` over an ordered device subset with the
  ``PartitionSpec``/``NamedSharding`` objects the bucketed kernels need:
  lane-sharded ``(E, S)``/``(E, S, R)`` operands and the replicated μop
  port-mask LUT.  Meshes are memoized per device-id tuple so repeated
  kernel-bucket compilations share one mesh object.

* **Per-device dispatch locks** — :func:`dispatch_lock` hands out one
  ``threading.Lock`` per *device subset* (keyed by sorted device ids,
  module-wide).  Machines placed on the same subset share a lock, so
  their GIL-bound kernel dispatch serializes exactly as the campaign-wide
  execute lock used to; machines on **disjoint subsets get different
  locks and their kernels never serialize** — the point of campaign
  device placement.  (Kernels already queued on one device serialize in
  XLA's per-device stream regardless; the lock only covers host-side
  dispatch.)

* **Campaign placement** — :func:`partition` splits the resolved devices
  into per-machine groups: contiguous disjoint blocks when there are at
  least as many devices as machines (a multi-uarch campaign becomes
  wall-clock-bound by one uarch), round-robin shared singletons
  otherwise.
"""
from __future__ import annotations

import os
import threading
from pathlib import Path

from repro.obs import tracer as obs

# user-facing device-count knob (int or "all"); unset means "all available"
ENV_DEVICES = "REPRO_SIM_DEVICES"

# JAX reads this itself; when it is set the program configures no cache
ENV_COMPILE_CACHE = "JAX_COMPILATION_CACHE_DIR"
# the compile cache's fixed home inside the checkout (the directory is part
# of every cache key, so it must not move between runs)
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def device_platform() -> str | None:
    """The platform jax computes on by default (``"cpu"``, ``"tpu"``, ...),
    or ``None`` when jax is not importable."""
    try:
        import jax  # noqa: PLC0415
    except ImportError:
        return None
    return jax.default_backend()


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache; call before a kernel's
    first compile.  With ``JAX_COMPILATION_CACHE_DIR`` set JAX already
    reads it and nothing is configured here; otherwise, on a TPU only, the
    cache goes to :data:`COMPILE_CACHE_DIR` and keeps every kernel,
    however fast its compile (CPU runs, and compiles for a described chip,
    write nothing).  Returns the cache directory in use, or ``None``."""
    env = os.environ.get(ENV_COMPILE_CACHE)
    if env:
        return env
    if device_platform() != "tpu":
        return None
    path = str(COMPILE_CACHE_DIR)
    import jax  # noqa: PLC0415
    if jax.config.jax_compilation_cache_dir != path:
        from jax.experimental.compilation_cache import (  # noqa: PLC0415
            compilation_cache)
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        # a cache already checked in this process kept no directory
        compilation_cache.reset_cache()
    return path


def jax_devices() -> tuple:
    """All jax devices, in jax's canonical order; ``()`` when jax is not
    importable (the numpy backend / scalar oracle need no devices)."""
    try:
        import jax  # noqa: PLC0415
    except ImportError:
        return ()
    return tuple(jax.devices())


def resolve_devices(spec=None) -> tuple:
    """Resolve a device spec to an ordered tuple of jax devices.

    ``spec`` may be ``None`` (read ``REPRO_SIM_DEVICES``, default
    ``"all"``), an integer count (clamped to ``[1, available]`` — asking
    for more devices than the host has degrades gracefully to all of
    them), the string ``"all"``, a decimal string, or an explicit
    sequence of jax devices (returned as-is).  Returns ``()`` when jax is
    unavailable."""
    if spec is None:
        spec = os.environ.get(ENV_DEVICES, "").strip() or "all"
    if isinstance(spec, str):
        s = spec.strip().lower()
        n = None if s == "all" else int(s)
    elif isinstance(spec, int):
        n = spec
    else:
        return tuple(spec)
    devs = jax_devices()
    if not devs or n is None:
        return devs
    return devs[:min(max(n, 1), len(devs))]


class LaneMesh:
    """A 1-D ``lanes`` mesh over an ordered device subset, plus the
    shardings the bucketed wave kernels use: ``spec2``/``spec3`` shard the
    leading (lane-major experiment) axis of ``(E, S)`` / ``(E, S, R)``
    operands across ``lanes``; ``replicated`` carries the μop port-mask
    LUT to every device once."""

    __slots__ = ("devices", "n", "mesh", "spec2", "spec3", "repl_spec",
                 "shard2", "shard3", "replicated")

    def __init__(self, devices):
        import numpy as np  # noqa: PLC0415
        from jax.sharding import (  # noqa: PLC0415
            Mesh, NamedSharding, PartitionSpec)
        self.devices = tuple(devices)
        self.n = len(self.devices)
        self.mesh = Mesh(np.array(self.devices), ("lanes",))
        self.spec2 = PartitionSpec("lanes", None)
        self.spec3 = PartitionSpec("lanes", None, None)
        self.repl_spec = PartitionSpec(None, None)
        self.shard2 = NamedSharding(self.mesh, self.spec2)
        self.shard3 = NamedSharding(self.mesh, self.spec3)
        self.replicated = NamedSharding(self.mesh, self.repl_spec)

    @property
    def key(self) -> tuple:
        """Cache identity: the ordered device-id tuple (kernel executables
        are bound to concrete devices, so it keys the AOT cache too)."""
        return tuple(d.id for d in self.devices)

    def __repr__(self):
        return f"<LaneMesh lanes={self.n} devices={list(self.key)}>"


_MESHES: dict = {}
_LOCKS: dict = {}
_REGISTRY_LOCK = threading.Lock()


def lane_mesh(devices) -> LaneMesh:
    """Memoized :class:`LaneMesh` for an ordered device tuple (meshes are
    shared across machines and kernel buckets)."""
    key = tuple(d.id for d in devices)
    with _REGISTRY_LOCK:
        m = _MESHES.get(key)
        if m is None:
            # mesh construction is the one-time cost worth seeing in a
            # trace (NamedSharding setup ahead of kernel compilation)
            with obs.span("mesh.build", devices=list(key)):
                m = _MESHES[key] = LaneMesh(devices)
        return m


def dispatch_lock(devices) -> threading.Lock:
    """The per-device-subset dispatch lock (module-wide, keyed by sorted
    device ids; the empty subset shares one host lock).  Machines placed
    on the same subset serialize their host-side kernel dispatch on it;
    disjoint subsets get independent locks, so their kernels never
    serialize behind one campaign-wide lock."""
    key = tuple(sorted(d.id for d in devices)) if devices else ("host",)
    with _REGISTRY_LOCK:
        lk = _LOCKS.get(key)
        if lk is None:
            lk = _LOCKS[key] = threading.Lock()
        return lk


def partition(devices, n_groups: int) -> list:
    """Split ``devices`` into ``n_groups`` placement groups for a
    campaign's machines: contiguous **disjoint** blocks (balanced to
    within one device) when ``len(devices) >= n_groups``, round-robin
    shared singletons when there are fewer devices than machines, and
    empty groups (single-device fallback: no placement) without jax."""
    devices = tuple(devices)
    if n_groups <= 0:
        return []
    d = len(devices)
    if d == 0:
        groups = [() for _ in range(n_groups)]
    elif d >= n_groups:
        groups = [devices[i * d // n_groups:(i + 1) * d // n_groups]
                  for i in range(n_groups)]
    else:
        groups = [(devices[i % d],) for i in range(n_groups)]
    if obs.enabled():
        # placement ids are trace-only; tests pass plain ints as devices
        obs.instant("mesh.partition", devices=d, groups=n_groups,
                    placement=[[getattr(dev, "id", dev) for dev in g]
                               for g in groups])
    return groups
