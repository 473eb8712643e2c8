"""Compile accounting and peak device memory (after ``chip_smoke.py``).

``CompileClock`` listens to ``jax.monitoring``: it sums the seconds XLA
spent compiling, and counts every program that had to be compiled or
loaded from the persistent cache. A count that moves inside the
measured window means something compiled there.
"""

from __future__ import annotations

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, secs: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += secs
            self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_loads += 1

    @property
    def programs(self) -> int:
        """Programs compiled or loaded from the cache so far."""
        return self.compiles + self.cache_loads

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_dur)
        jax.monitoring.unregister_event_listener(self._on_event)


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
