"""Host spans the benchmark records around its calls into the program.

Each span is written twice: into a list on the host clock
(``time.perf_counter_ns``) and, as ``bench.<name>``, into the profiler's
trace when one is being taken, so that device idle time can be attributed
to what the host was doing.
"""
from __future__ import annotations

import contextlib
import time

import jax


class Spans:
    def __init__(self):
        self.rows: list = []          # [name, start_ns, end_ns]

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation("bench." + name):
            try:
                yield
            finally:
                self.rows.append([name, t0, time.perf_counter_ns()])
