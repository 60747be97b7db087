"""Compile seconds and persistent-cache hits from JAX's monitoring
events.  A copy of ``chip_smoke.py::CompileLog`` with per-window
counting."""

from __future__ import annotations

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration
            self.names.append(kw.get("fun_name", "?"))

    def _event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def mark(self) -> tuple:
        return (self.compiles, self.compile_s, self.cache_hits, len(self.names))

    def since(self, mark: tuple) -> dict:
        c, s, h, n = mark
        return {
            "compiles": self.compiles - c,
            "compile_s": self.compile_s - s,
            "cache_hits": self.cache_hits - h,
            "compiled": sorted(set(self.names[n:])),
        }
