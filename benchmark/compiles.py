"""Counts what compiles: every executable JAX asks its compilation cache
for (a hit loads, a miss compiles: either way a new shape met the window),
beside the program's own registry of its profiled programs."""

from __future__ import annotations


class CompileWatch:
    def __init__(self):
        from jax import monitoring

        from pathway_tpu.obs import profiler

        self._registry = profiler.registry()
        self.requests = 0
        monitoring.register_event_listener(self._event)

    def _event(self, name: str, **_kw) -> None:
        if name in ("/jax/compilation_cache/cache_hits",
                    "/jax/compilation_cache/cache_misses"):
            self.requests += 1

    def count(self) -> int:
        return self.requests + self._registry.total_compiles()
