"""Seconds JAX spends tracing, lowering and compiling (or reading the
persistent cache), and the persistent-cache hits."""

from __future__ import annotations

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    # wraps the backend compile, or the read of a persistent-cache hit
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Counts from construction (or ``reset``) until ``close``."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kw):
        if event in COMPILE_EVENTS:
            self.seconds += duration_secs
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reset(self):
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
