"""Seconds spent getting executables, and the persistent cache's hits and
misses, from JAX's monitoring events (copied from ``chip_smoke.py``)."""

from __future__ import annotations

__all__ = ["CompileLog"]


class CompileLog:
    def __init__(self):
        import jax
        self.seconds = 0.0
        self.executables = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.executables += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def state(self) -> tuple:
        return self.seconds, self.executables, self.hits, self.misses

    def since(self, before: tuple) -> dict:
        now = self.state()
        return {"compile_s": now[0] - before[0],
                "executables": now[1] - before[1],
                "cache_hits": now[2] - before[2],
                "cache_misses": now[3] - before[3]}
