"""Compilation, counted from JAX's own monitoring events.

``built`` counts programs made ready to run (a backend compile or a read
from the persistent cache), ``traced`` counts functions traced to a jaxpr,
and ``misses`` counts programs the persistent cache did not hold, that is
real compilations.  ``seconds`` sums the durations of the
``/jax/core/compile/`` events.  Inside a measured window all three counts
should stay at zero.
"""
from __future__ import annotations

import jax

TRACE = "/jax/core/compile/jaxpr_trace_duration"
BUILD = "/jax/core/compile/backend_compile_duration"
MISS = "/jax/compilation_cache/cache_misses"


class CompileClock:
    def __init__(self):
        self.seconds = 0.0
        self.traced = 0
        self.built = 0
        self.misses = 0
        self.names: list = []        # functions built, in order

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
        if event == TRACE:
            self.traced += 1
        elif event == BUILD:
            self.built += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def _on_event(self, event: str, **_) -> None:
        if event == MISS:
            self.misses += 1

    def start(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def counts(self) -> dict:
        return {"traced": self.traced, "built": self.built,
                "misses": self.misses, "seconds": self.seconds}
