"""Spans and counters inside the program, off unless a caller records.

The hot path calls :func:`span` and :func:`count` at its layer boundaries
(``bcd.step``, ``bcd.select``, ``engine.stage``, ``snl.finetune``, ...).
While nothing records, both cost one module-level check: :func:`span`
returns a shared no-op context, :func:`count` returns at once, and no JAX
listener is registered.

``with recording() as rec:`` turns them on for the block:

* each span is kept as ``Span(name, t0, t1, parent)`` in ``rec.spans`` on
  ``time.perf_counter``'s clock, ``parent`` being the index in ``rec.spans``
  of the span that was open around it (``None`` at the top), and is also
  written into any profiler trace as a ``jax.profiler.TraceAnnotation``
  named ``repro.<name>``;
* JAX's own compile phases arrive through ``jax.monitoring`` as spans
  ``jax.trace`` (tracing to a jaxpr), ``jax.lower`` (lowering to MLIR) and
  ``jax.compile`` (XLA compilation, or the load from the persistent cache),
  with the function's name in ``fun_name``, under the innermost open
  program span; a phase that nests inside another of its kind (the trace of
  a jit inside the trace of its caller) is merged into the outer one;
* counts go to ``rec.counts``, and while a ``bcd.step`` span is open also
  to that step's dict in ``rec.step_counts``; JAX's persistent-cache hits
  and misses are counted as ``jax.cache_hit`` and ``jax.cache_miss``.

Everything stays in memory; the caller writes it out when it is done.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

STEP = "bcd.step"
PROFILER_PREFIX = "repro."

_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "jax.trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
           "/jax/core/compile/backend_compile_duration": "jax.compile"}
_EVENTS = {"/jax/compilation_cache/cache_hits": "jax.cache_hit",
           "/jax/compilation_cache/cache_misses": "jax.cache_miss"}


class Span(NamedTuple):
    name: str
    t0: float                      # time.perf_counter seconds
    t1: float
    parent: Optional[int]          # index in Recorder.spans
    fun_name: Optional[str] = None  # jax.* spans: the function compiled


class Recorder:
    """What one :func:`recording` block saw."""

    def __init__(self):
        self.spans: List[Optional[Span]] = []
        self.counts: Dict[str, int] = collections.Counter()
        self.step_counts: List[Dict[str, int]] = []
        self._lock = threading.Lock()
        self._local = threading.local()
        self._step: Optional[Dict[str, int]] = None
        # JAX stamps its spans with time.time(); this moves them onto
        # time.perf_counter's clock
        a = time.perf_counter()
        wall = time.time()
        self._offset = 0.5 * (a + time.perf_counter()) - wall

    def _stack(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _open(self, name: str) -> int:
        stack = self._stack()
        with self._lock:
            i = len(self.spans)
            self.spans.append(None)        # filled in when the span closes
        stack.append(i)
        if name == STEP:
            self._step = collections.Counter()
            self.step_counts.append(self._step)
        return i

    def _close(self, i: int, name: str, t0: float, t1: float) -> None:
        stack = self._stack()
        stack.remove(i)
        self.spans[i] = Span(name, t0, t1, stack[-1] if stack else None)
        if name == STEP:
            self._step = None

    def count(self, name: str, n: int) -> None:
        self.counts[name] += n
        if self._step is not None:
            self._step[name] += n

    def _on_span(self, event: str, t0: float, t1: float, **kw) -> None:
        name = _PHASES.get(event)
        if name is None:
            return
        t0, t1 = t0 + self._offset, t1 + self._offset
        stack = self._stack()
        parent = stack[-1] if stack else None
        with self._lock:
            # an inner phase of the same kind ends first: fold it into this
            while self.spans and self.spans[-1] is not None and \
                    self.spans[-1].name == name and \
                    self.spans[-1].t0 >= t0 and self.spans[-1].t1 <= t1:
                self.spans.pop()
            self.spans.append(Span(name, t0, t1, parent, kw.get("fun_name")))

    def _on_event(self, event: str, **kw) -> None:
        name = _EVENTS.get(event)
        if name is not None:
            self.count(name, 1)


class _Off:
    """The shared context :func:`span` returns while nothing records."""

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()
_rec: Optional[Recorder] = None    # the recording in progress, if any


class _On:
    __slots__ = ("rec", "name", "i", "t0", "ann")

    def __init__(self, rec: Recorder, name: str):
        self.rec, self.name = rec, name

    def __enter__(self):
        self.i = self.rec._open(self.name)
        self.ann = jax.profiler.TraceAnnotation(PROFILER_PREFIX + self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return None

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        self.rec._close(self.i, self.name, self.t0, t1)
        return False


def span(name: str):
    """A context that records ``name`` while :func:`recording` is on."""
    rec = _rec
    if rec is None:
        return _OFF
    return _On(rec, name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` while :func:`recording` is on."""
    rec = _rec
    if rec is not None:
        rec.count(name, n)


@contextlib.contextmanager
def recording():
    """Record spans, counts and JAX's compile phases for the block; yields
    the :class:`Recorder`.  One recording at a time."""
    global _rec
    if _rec is not None:
        raise RuntimeError("tracing.recording() is already on")
    rec = Recorder()
    jax.monitoring.register_event_time_span_listener(rec._on_span)
    jax.monitoring.register_event_listener(rec._on_event)
    _rec = rec
    try:
        yield rec
    finally:
        _rec = None
        jax.monitoring.unregister_event_time_span_listener(rec._on_span)
        jax.monitoring.unregister_event_listener(rec._on_event)
