"""Counts XLA compilations and persistent-cache hits through ``jax.monitoring``
(copied from chip_smoke.CompileMeter).  Every backend compile, a load from the
persistent cache included, fires one ``backend_compile_duration``; a load also
fires ``cache_hits``.  So ``compiles - cache_hits`` is what XLA really
compiled, and that has to be 0 inside a measured window."""

from __future__ import annotations


class CompileMeter:
    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def mark(self) -> tuple:
        return (self.compiles, self.compile_s, self.hits, self.misses)

    def since(self, mark: tuple) -> dict:
        compiles, hits = self.compiles - mark[0], self.hits - mark[2]
        return {
            "compiled": max(0, compiles - hits),
            "cache_loads": hits,
            "cache_misses": self.misses - mark[3],
            "compile_s": self.compile_s - mark[1],
        }
