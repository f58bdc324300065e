"""What JAX compiled, and when: copied from ``chip_smoke.CompileWatch``."""

from __future__ import annotations


class CompileWatch:
    """Counts backend compilations (and their seconds) and persistent-
    cache hits through ``jax.monitoring``, from construction on."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.compile_s, self.cache_hits
