"""Counters the benchmark reads from JAX itself, not from the program."""
from __future__ import annotations


class CompileCounter:
    """Executables compiled or loaded from JAX's persistent cache, and the
    cache hits among them, from JAX's own monitoring events."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == self.COMPILE_EVENT:
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == self.CACHE_HIT_EVENT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
