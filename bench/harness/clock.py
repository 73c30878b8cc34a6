"""JAX's own compile events, counted (a copy of ``chip_smoke.CompileClock``,
with the persistent cache's hits counted beside it)."""

from __future__ import annotations

COMPILE_PREFIX = "/jax/core/compile/"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (every
    ``/jax/core/compile/*`` duration event), and how many backend compiles
    the persistent cache did not answer: the XLA compiles proper."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.backend_compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event.startswith(COMPILE_PREFIX):
            self.seconds += secs
        if event == BACKEND_COMPILE:
            self.backend_compiles += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1

    @property
    def xla_compiles(self) -> int:
        return self.backend_compiles - self.cache_hits

    def reading(self) -> tuple[float, int]:
        return self.seconds, self.xla_compiles
