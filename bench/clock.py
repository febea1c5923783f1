"""Seconds and events of JAX compilation, from ``jax.monitoring``.

Taken from ``chip_smoke.py``'s ``CompileClock``.  Set-up reads the
seconds and the persistent cache's hits and misses; the window reads the
compiles, since nothing may compile in it.
"""
from __future__ import annotations

import jax

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE = {"/jax/compilation_cache/cache_hits": "cache_hits",
         "/jax/compilation_cache/cache_misses": "cache_misses"}


class CompileClock:
    """Running totals of compile seconds, backend compiles and persistent
    cache hits and misses in this process since the clock was made."""

    def __init__(self):
        self.seconds = 0.0
        self.by_event = {e.rsplit("/", 1)[1]: 0.0 for e in EVENTS}
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in EVENTS:
            self.seconds += duration
            self.by_event[event.rsplit("/", 1)[1]] += duration
        if event == BACKEND:
            self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        name = CACHE.get(event)
        if name:
            setattr(self, name, getattr(self, name) + 1)
