"""Host spans and per-step records of the benchmark's own calls into the
program.

Each span is written twice: into the profiler's trace as a
``jax.profiler.TraceAnnotation`` named ``bench.<name>`` (so that idle gaps
on the device can be attributed to what the host was doing), and into an
in-memory list on ``time.perf_counter``'s clock (so that per-layer metrics
need no trace).  Spans cost a list append when the profiler is off.
"""
from __future__ import annotations

import contextlib
import time
from typing import List, Tuple

import jax

PREFIX = "bench."


class Recorder:
    def __init__(self):
        self.spans: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation(PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))
