"""XLA compile count and seconds, and the persistent cache's hits and writes.

Copied from ``CompileMeter`` in ``chip_smoke.py`` (commit 4c81949), so that
the yardstick stays put when the program changes.  A persistent-cache hit
counts as a compile, at its load time.
"""
from __future__ import annotations


class CompileMeter:
    """Process-wide counts from jax's monitoring events."""

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
                "xla_compile_s": self.seconds - s,
                "compile_cache_hits": self.cache_hits - h,
                "compile_cache_writes": self.cache_writes - w}
