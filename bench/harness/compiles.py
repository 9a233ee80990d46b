"""Programs compiled and loaded from the persistent cache, read from
``jax.monitoring`` (copied from the repository's chip smoke run)."""

from __future__ import annotations


class CompileCounter:
    """The backend-compile event also fires for a program loaded from the
    persistent cache, so fresh compiles are events - hits."""

    def __init__(self) -> None:
        from jax import monitoring

        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, int]:
        """(fresh compiles, persistent-cache hits) so far."""
        return self.compiles - self.cache_hits, self.cache_hits
