"""Compile events, counted through ``jax.monitoring``: every backend
compile, and every program fetched from the persistent cache, fires
``/jax/core/compile/backend_compile_duration`` once."""

from __future__ import annotations

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    def __init__(self):
        self.events = 0
        self.cache_hits = 0

    def _on_duration(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.events += 1

    def _on_event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
