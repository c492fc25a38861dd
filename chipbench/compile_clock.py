"""Backend compiles and persistent-cache hits, from JAX's monitoring events.

A copy of ``chip_smoke._CompileClock``: the benchmark counts compiles in
its own code, so a change to the smoke script cannot move it.  A cache hit
is timed as its read.
"""

from __future__ import annotations


class CompileClock:
    def __init__(self):
        import jax

        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits
