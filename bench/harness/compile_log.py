"""Counts backend compiles (and persistent-cache reads, which JAX times
under the same event) and persistent-cache hits and misses, through
``jax.monitoring`` listeners.  Copied from ``chip_smoke.py``."""
from __future__ import annotations


class CompileLog:
    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0

        def on_duration(name, secs, **_):
            if name == '/jax/core/compile/backend_compile_duration':
                self.seconds += secs
                self.compiles += 1

        def on_event(name, **_):
            if name == '/jax/compilation_cache/cache_hits':
                self.hits += 1
            elif name == '/jax/compilation_cache/cache_misses':
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
