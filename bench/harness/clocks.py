"""Compile accounting from jax's monitoring events.

jax records ``/jax/core/compile/backend_compile_duration`` around every
``compile_or_get_cached`` call, a load from the persistent compilation
cache included, and ``/jax/compilation_cache/cache_hits`` on each such
load.  So XLA compiles = backend-compile events - cache hits.
"""
from __future__ import annotations

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Counts backend compiles, cache loads and their seconds."""

    def __init__(self):
        self.backend_events = 0
        self.cache_hits = 0
        self.seconds = 0.0

    def install(self, jax) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, name, duration, **_):
        if name in (BACKEND_COMPILE, LOWERING):
            self.seconds += duration
        if name == BACKEND_COMPILE:
            self.backend_events += 1

    def _on_event(self, name, **_):
        if name == CACHE_HIT:
            self.cache_hits += 1

    @property
    def xla_compiles(self) -> int:
        return self.backend_events - self.cache_hits

    def snapshot(self) -> dict:
        return {"xla_compiles": self.xla_compiles,
                "cache_loads": self.cache_hits,
                "compile_s": self.seconds}

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {k: after[k] - before[k] for k in after}
