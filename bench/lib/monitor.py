"""Counts of the programs JAX traces and compiles, from `jax.monitoring`.

`Counter.snapshot()` before and after a window gives how many programs
were traced, how many reached the backend's compile step, and how many
of those the persistent cache served; the difference of the last two is
the number XLA really compiled.
"""
from __future__ import annotations

import collections

TRACE = "/jax/core/compile/jaxpr_trace_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Counter:
    def __init__(self):
        import jax

        self.counts = collections.Counter()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        self.counts[name] += 1

    def _duration(self, name, _secs, **_):
        self.counts[name] += 1

    def snapshot(self) -> dict:
        return {"traced": self.counts[TRACE], "backend": self.counts[BACKEND],
                "cache_hits": self.counts[CACHE_HIT]}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        d = {k: b[k] - a[k] for k in a}
        d["compiled"] = d["backend"] - d["cache_hits"]
        return d
