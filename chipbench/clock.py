"""Compile counting and the percentile the end-to-end tails use.

``CompileClock`` is copied from ``chip_smoke.py`` and ``percentile`` from
``repro.obs.metrics``; the benchmark keeps its own copies so that no change
to the program can change how it is measured.
"""

from __future__ import annotations

from typing import Sequence

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds XLA spent compiling (persistent-cache reads included), and
    the number of compiles, read off JAX's own monitoring events.  Tracing
    and lowering are left out: their events nest, so summing them would
    count time twice."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == _BACKEND_COMPILE:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    def mark(self):
        return self.seconds, self.compiles, self.cache_hits


def percentile(values: Sequence[float], q: float) -> float:
    """Exact empirical quantile (nearest rank): the element at
    floor(q * n), clamped into range; ``nan`` on an empty sample."""
    if not len(values):
        return float("nan")
    vals = sorted(values)
    return vals[min(int(q * len(vals)), len(vals) - 1)]
