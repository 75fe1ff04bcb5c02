"""The measured window, and the profiler's part of it.

The window starts when the job enters it and closes at the first step
boundary after ``seconds``.  With a trace directory the profiler records
from the start of the window to the first step boundary after
``trace_s`` seconds (a trace of the whole window would be too large to
read back in a run's time), inside one ``bench.window`` host span.
"""
from __future__ import annotations

import time
from typing import Optional

import jax

from . import spans as spans_lib


class Window:
    def __init__(self, seconds: float, trace_dir: Optional[str] = None,
                 trace_s: float = 10.0):
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        self.trace_s = min(float(trace_s), self.seconds)
        self.t0 = self.t1 = 0.0
        self.trace_end: Optional[float] = None
        self._ann = None

    def __enter__(self):
        if self.trace_dir:
            jax.profiler.start_trace(self.trace_dir)
            self._ann = jax.profiler.TraceAnnotation(
                spans_lib.PREFIX + "window")
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def _stop_trace(self, now: float) -> None:
        self._ann.__exit__(None, None, None)
        self._ann = None
        self.trace_end = now
        jax.profiler.stop_trace()

    def running(self) -> bool:
        """Called at every step boundary: False once the window is over."""
        now = time.perf_counter()
        if self._ann is not None and now - self.t0 >= self.trace_s:
            self._stop_trace(now)
        return now - self.t0 < self.seconds

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._stop_trace(self.t1)
        return False

    @property
    def elapsed(self) -> float:
        """Seconds from the start of the window to its last step's end."""
        return self.t1 - self.t0

    @property
    def traced(self):
        """(start, end) of the traced part on the host clock, or None."""
        if self.trace_end is None:
            return None
        return self.t0, self.trace_end
