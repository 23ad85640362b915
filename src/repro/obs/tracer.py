"""Thread-safe hierarchical spans with a near-zero disabled fast path.

Every instrumented seam in the repo calls the *module-level* helpers::

    from repro import obs

    with obs.span("wave.execute", wave=len(codes)) as sp:
        ...
        sp.set(chunks=n)          # attach attributes mid-span

When tracing is disabled (the default) ``obs.span(...)`` returns a single
shared stateless no-op context manager — one attribute load, one truth
test, no allocation — so instrumentation costs a few tens of nanoseconds
per call site and nothing else.  Enable globally with ``REPRO_TRACE=1`` in
the environment, or programmatically::

    obs.set_tracer(obs.Tracer(enabled=True))
    ... traced work ...
    events = obs.get_tracer().events()

Design notes
------------
* Clocks are ``time.perf_counter_ns()`` (monotonic); export converts to
  the trace-event µs epoch relative to the tracer's start.
* Events append to one shared list — ``list.append`` is atomic under the
  GIL, so the hot path takes no lock.
* Each thread keeps its own span stack (``threading.local``) so nesting
  is tracked per thread and the tracer is reentrant across the Campaign
  pool.  A ``trace_id`` attribute set on an enclosing span is inherited
  by child spans on the same thread (how server request IDs flow into
  batch-predictor spans without threading them through every signature).
* Each span also records the thread's CPU time (``time.thread_time_ns``)
  as ``cpu`` beside ``dur``: for a span on one thread, wall minus CPU is
  time the thread waited (for the interpreter lock, a lock or the device).
* While a tracer is enabled and jax can be imported, every span (and every
  timed lock wait) also enters a ``jax.profiler.TraceAnnotation`` of the
  same name on the thread that runs it, so a profiler trace carries the
  program's spans on the device trace's clock, one host line per thread.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional

_now = time.perf_counter_ns
_cpu = time.thread_time_ns

_ANNOTATION = ()     # jax.profiler.TraceAnnotation, None without jax


def _annotation():
    """The profiler's annotation class, imported on first use by an
    enabled tracer; ``None`` when jax cannot be imported."""
    global _ANNOTATION
    if _ANNOTATION == ():
        try:
            from jax.profiler import TraceAnnotation  # noqa: PLC0415
        except ImportError:
            TraceAnnotation = None
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


class _NullSpan:
    """Shared no-op span: returned by every helper while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


NULL_SPAN = _NullSpan()


class _Span:
    """A live span: records one complete ("X") event when it exits, and
    holds a profiler annotation of the same name open meanwhile."""

    __slots__ = ("_tracer", "name", "args", "_ann", "_t0", "_c0")

    def __init__(self, tracer: "Tracer", name: str,
                 args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self.name = name
        self.args = args

    def set(self, **attrs) -> "_Span":
        """Attach (or overwrite) key/value attributes mid-span."""
        if self.args is None:
            self.args = attrs
        else:
            self.args.update(attrs)
        return self

    def __enter__(self):
        self._tracer._stack().append(self)
        ann = _annotation()
        if ann is not None:
            self._ann = ann(self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        self._c0 = _cpu()
        self._t0 = _now()
        return self

    def __exit__(self, *exc):
        t1 = _now()
        c1 = _cpu()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        tr = self._tracer
        stack = tr._stack()
        if stack and stack[-1] is self:
            stack.pop()
        args = self.args
        if (args is None or "trace_id" not in args):
            # inherit the nearest enclosing trace_id on this thread
            for sp in reversed(stack):
                a = sp.args
                if a is not None and "trace_id" in a:
                    args = dict(args) if args else {}
                    args["trace_id"] = a["trace_id"]
                    break
        tr._emit("X", self.name, self._t0, t1 - self._t0, args,
                 c1 - self._c0)
        return False


class _LockWait:
    """Context manager that times lock acquisition separately from the
    critical section, so contention shows up as its own span.

    Drives the lock through the context-manager protocol (not
    ``acquire``/``release``) so any ``with``-able lock the call sites
    already accepted keeps working; ``lock=None`` degrades to a pure
    no-op (the existing "no lock configured" behaviour is preserved
    bit-for-bit)."""

    __slots__ = ("_lock", "_name", "_tracer")

    def __init__(self, lock, name: str, tracer: Optional["Tracer"]):
        self._lock = lock
        self._name = name
        self._tracer = tracer

    def __enter__(self):
        if self._lock is None:
            return self
        tr = self._tracer
        if tr is None:
            self._lock.__enter__()
            return self
        with _Span(tr, self._name, None):
            self._lock.__enter__()
        return self

    def __exit__(self, *exc):
        if self._lock is not None:
            return self._lock.__exit__(*(exc or (None, None, None)))
        return False


class Tracer:
    """Collects trace events; one instance is installed globally.

    Thread-safe by construction: the event sink is a plain list (append is
    GIL-atomic) and span stacks are per-thread."""

    def __init__(self, enabled: bool = False):
        self.enabled = bool(enabled)
        self.t0_ns = _now()
        self.pid = os.getpid()
        self._events: List[dict] = []
        self._local = threading.local()
        self._threads: Dict[int, str] = {}

    # -- hot path ------------------------------------------------------
    def span(self, name: str, **attrs):
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, attrs or None)

    def instant(self, name: str, **attrs):
        """A zero-duration marker event."""
        if self.enabled:
            self._emit("i", name, _now(), 0, attrs or None)

    def counter(self, name: str, value):
        """A sampled counter value (a counter track in Perfetto)."""
        if self.enabled:
            self._emit("C", name, _now(), 0, {"value": value})

    def wait_lock(self, lock, name: str = "lock.wait"):
        if not self.enabled:
            return _LockWait(lock, name, None)
        return _LockWait(lock, name, self)

    # -- plumbing ------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _emit(self, ph: str, name: str, t0: int, dur: int,
              args: Optional[dict], cpu: Optional[int] = None):
        tid = threading.get_ident()
        if tid not in self._threads:
            self._threads[tid] = threading.current_thread().name
        ev = {"ph": ph, "name": name, "t0": t0, "dur": dur, "tid": tid,
              "args": args}
        if cpu is not None:
            ev["cpu"] = cpu
        self._events.append(ev)

    def events(self) -> List[dict]:
        """The raw event list (internal schema; see export.py for the
        Chrome trace-event rendering)."""
        return list(self._events)

    def clear(self):
        self._events.clear()
        self._threads.clear()
        self.t0_ns = _now()

    def thread_names(self) -> Dict[int, str]:
        return dict(self._threads)


def _from_env() -> Tracer:
    flag = os.environ.get("REPRO_TRACE", "")
    return Tracer(enabled=flag not in ("", "0", "false", "off"))


_GLOBAL: Tracer = _from_env()


def get_tracer() -> Tracer:
    return _GLOBAL


def set_tracer(tracer: Tracer) -> Tracer:
    """Install ``tracer`` globally; returns the previous tracer so tests
    and benches can restore it."""
    global _GLOBAL
    prev = _GLOBAL
    _GLOBAL = tracer
    return prev


def enabled() -> bool:
    return _GLOBAL.enabled


# Module-level helpers: the instrumented call sites use these.  Each is a
# single global load + truth test when tracing is off.
def span(name: str, **attrs):
    t = _GLOBAL
    if not t.enabled:
        return NULL_SPAN
    return _Span(t, name, attrs or None)


def instant(name: str, **attrs):
    t = _GLOBAL
    if t.enabled:
        t._emit("i", name, _now(), 0, attrs or None)


def counter(name: str, value):
    t = _GLOBAL
    if t.enabled:
        t._emit("C", name, _now(), 0, {"value": value})


def wait_lock(lock, name: str = "lock.wait"):
    t = _GLOBAL
    if not t.enabled:
        return _LockWait(lock, name, None)
    return _LockWait(lock, name, t)

