"""What jax compiled, and when: read from jax's own monitoring events.

Copied from chip_smoke.py::CompileClock; added: a count of programs built
or loaded, so a run can state that none was inside the measured window.
"""
from __future__ import annotations


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling, the number of
    backend compilations, and persistent-cache hits / misses."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.backend_compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"seconds": self.seconds,
                "backend_compiles": self.backend_compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in after}

    @staticmethod
    def programs_built(delta: dict) -> int:
        """Programs compiled or loaded from the persistent cache: each is
        an executable that was not ready when the interval began."""
        return int(delta["backend_compiles"] + delta["cache_hits"])
