"""Compile seconds from JAX's monitoring events, split as set-up reports them.

Trace+lower is what no persistent cache removes; backend is the compiler
itself, or the fetch of its result from the persistent cache, the part the
cache shortens.  ``compiles`` counts backend compiles and cache fetches, so
a count taken over the measured window shows any compile inside it.
"""

from __future__ import annotations

_TRACE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        self.trace_s = 0.0
        self.backend_s = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def install(self) -> "CompileClock":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    @property
    def seconds(self) -> float:
        return self.trace_s + self.backend_s

    def snapshot(self) -> dict:
        return {
            "trace_s": self.trace_s,
            "backend_s": self.backend_s,
            "compiles": self.compiles,
            "cache_hits": self.cache_hits,
        }

    def _on_duration(self, event, duration, **_):
        if event in _TRACE_EVENTS:
            self.trace_s += duration
        elif event == _BACKEND_EVENT:
            self.backend_s += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1
