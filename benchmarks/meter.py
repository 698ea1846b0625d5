"""Compile seconds and persistent-cache traffic from JAX's own monitoring
events (copied from ``chip_smoke.py``'s third meter, PR 21): tracing,
lowering and backend compile-or-load nest, so the seconds are the length of
the union of their intervals."""

from __future__ import annotations

import time

_COMPILE = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_BACKEND = "/jax/core/compile/backend_compile_duration"


def union_seconds(intervals, since: float = float("-inf"),
                  until: float = float("inf")) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[since, until]``."""
    total, covered_to = 0.0, since
    for start, end in sorted(intervals):
        start, end = max(start, covered_to), min(end, until)
        if end > start:
            total += end - start
            covered_to = end
    return total


class CompileMeter:
    """Listens from construction on; one per process."""

    def __init__(self):
        import jax.monitoring

        self.intervals: list[tuple[float, float]] = []
        self.backend_compiles: list[float] = []  # end times
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event in _COMPILE:
            end = time.perf_counter()  # the listener fires as the event ends
            self.intervals.append((end - seconds, end))
            if event == _BACKEND:
                self.backend_compiles.append(end)

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def compile_seconds(self, since: float, until: float = float("inf")):
        return union_seconds(self.intervals, since, until)

    def compiles_between(self, start: float, end: float) -> int:
        """Backend compiles (or cache loads) that ended inside
        ``[start, end]``: the count that has to be 0 in a measured window."""
        return sum(1 for t in self.backend_compiles if start <= t <= end)
