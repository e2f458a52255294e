"""TraceWatch: count XLA trace/compile events per labeled region.

The 1F1B schedule was suspected of re-tracing per slot (ROADMAP item 3 /
the −26% CPU gap vs GPipe).  Timing can't distinguish "retraced" from
"just slow", but jax can: ``jax.monitoring`` fires a
``/jax/core/compile/...`` event-duration callback every time something
is traced, lowered or compiled — and stays silent on jit cache hits.
``TraceWatch`` turns that into an assertable invariant:

    with TraceWatch() as watch:
        with watch.region("warmup"):
            step(state)                  # traces: fine, it's the first call
        with watch.region("steady"):
            for _ in range(5):
                step(state)
    watch.assert_no_trace("steady")      # raises RetraceError on retrace

Counts are per *event*, so a single retraced jit typically shows several
events (trace + MLIR lowering + backend compile per executable); the
assertion only cares whether the count is zero.  Regions may be entered
repeatedly; counts accumulate under the same label.

Listeners are process-global in jax, so ``TraceWatch`` is a context
manager that unregisters its own listener on exit
(``clear_event_listeners`` would nuke other listeners).  Events raised outside any active region are accumulated
under the ``(unlabeled)`` pseudo-region rather than dropped.
"""
from __future__ import annotations

import contextlib
from collections import Counter
from typing import Iterator, Optional

UNLABELED = "(unlabeled)"

# event-name prefix that marks tracing/lowering/compilation work
TRACE_EVENT_PREFIX = "/jax/core/compile/"


class RetraceError(AssertionError):
    """A region that must be trace-free saw trace/compile events."""


class TraceWatch:
    def __init__(self) -> None:
        self.counts: Counter = Counter()          # label -> event count
        self.events: Counter = Counter()          # (label, event) -> count
        self._label: Optional[str] = None
        self._registered = False

    # -- listener plumbing -------------------------------------------------
    def _callback(self, event: str, duration: float, **kwargs) -> None:
        if event.startswith(TRACE_EVENT_PREFIX):
            label = self._label if self._label is not None else UNLABELED
            self.counts[label] += 1
            self.events[(label, event)] += 1

    def __enter__(self) -> "TraceWatch":
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._callback)
        self._registered = True
        return self

    def __exit__(self, *exc) -> None:
        if self._registered:
            import jax.monitoring
            jax.monitoring.unregister_event_duration_listener(self._callback)
            self._registered = False

    # -- regions -----------------------------------------------------------
    @contextlib.contextmanager
    def region(self, label: str) -> Iterator[None]:
        """Attribute trace events raised inside the block to ``label``.
        Regions don't nest (the inner label wins until it exits)."""
        prev, self._label = self._label, label
        try:
            yield
        finally:
            self._label = prev

    # -- queries -----------------------------------------------------------
    def traces(self, label: str) -> int:
        return self.counts.get(label, 0)

    def report(self) -> dict:
        """``{label: event_count}`` for every region seen (diffable)."""
        return dict(sorted(self.counts.items()))

    def assert_no_trace(self, label: str) -> None:
        n = self.traces(label)
        if n:
            detail = ", ".join(
                f"{event.rsplit('/', 1)[-1]}×{cnt}"
                for (lbl, event), cnt in sorted(self.events.items())
                if lbl == label)
            raise RetraceError(
                f"region {label!r} must be trace-free but saw {n} "
                f"trace/compile event(s): {detail} — a jit cache miss in "
                f"steady state (shape/dtype drift or an uncached closure)")
