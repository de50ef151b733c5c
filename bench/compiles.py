"""Compiles and persistent-cache loads, counted from JAX's own monitoring
events.

A listener registered with ``jax.monitoring`` cannot be removed, so one
counter lives for the process; callers read :meth:`CompileCounter.snapshot`
before and after the stretch they care about.
"""

from __future__ import annotations

import jax


class CompileCounter:
    """Backend compiles (and their seconds) and persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.cache_hits,
                "compile_s": self.seconds}
