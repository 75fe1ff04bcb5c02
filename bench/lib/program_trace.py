"""The program's spans and JAX's compile phases on the profiler's clock, and
the device's idle time put down to them.

A traced run's ``.xplane.pb`` holds, on the host planes and on the
profiler's one clock:

* ``repro.<name>``: the program's own spans (``repro.core.tracing``), when
  the program recorded while the profiler ran;
* the profiler's Python-call events of JAX's three compile phases:
  ``trace_to_jaxpr`` (tracing a jit to a jaxpr), ``_cached_lowering_to_hlo``
  (lowering to MLIR) and ``_cached_compilation`` (XLA compilation, or the
  executable's load from the persistent cache), read here as ``jax.trace``,
  ``jax.lower`` and ``jax.compile``.  They are the calls inside which JAX
  times its ``/jax/core/compile/*`` monitoring spans (the recorder's
  ``jax.*`` spans), and the profiler's Python tracer, on by default in
  ``jax.profiler.start_trace``, records them in every traced run of any
  version of the program.  The names are JAX's own (jax 0.9.0): a JAX
  that renames them leaves these phases out.

A ``repro.core.tracing.Recorder`` keeps the same spans on
``time.perf_counter``'s clock; :func:`to_profiler_clock` moves them onto
the profiler's, anchored on the ``bench.window`` span (``Window`` takes its
``t0`` as it enters that span and ``trace_end`` as it leaves it), and
raises where the two ends disagree by more than :data:`ANCHOR_TOL_S`.
"""
from __future__ import annotations

import heapq
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import trace as trace_lib
from .spec import ROOT
from .trace import Event, Trace

PROGRAM = "repro."
ANCHOR_TOL_S = 1e-3
_PHASE = re.compile(r"^\$\S+:\d+ (trace_to_jaxpr|_cached_lowering_to_hlo|"
                    r"_cached_compilation)$")
PHASES = {"trace_to_jaxpr": "jax.trace",
          "_cached_lowering_to_hlo": "jax.lower",
          "_cached_compilation": "jax.compile"}
NO_SPAN = "host (no program span)"


def host_events(path: str) -> Optional[List[Event]]:
    """The program's ``repro.*`` spans (prefix stripped) and JAX's compile
    phases (``jax.trace`` / ``jax.lower`` / ``jax.compile``) in a trace;
    ``None`` where it holds no Python-call event at all (the profiler's
    Python tracer was off, so the compile phases cannot be seen)."""
    from jax.profiler import ProfileData
    out, python_calls = [], False
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM):
                    name = e.name[len(PROGRAM):]
                elif e.name.startswith("$"):
                    python_calls = True
                    m = _PHASE.match(e.name)
                    if m is None:
                        continue
                    name = PHASES[m.group(1)]
                else:
                    continue
                out.append(Event(name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9))
    return out if python_calls else None


def is_jax(name: str) -> bool:
    return name.startswith("jax.")


def to_profiler_clock(spans: Iterable, anchors: Tuple[float, float],
                      window: Tuple[float, float]) -> List[Event]:
    """Recorder spans (``name, t0, t1, ...`` on ``time.perf_counter``) as
    Events on the profiler's clock.  ``anchors`` is the ``bench.window``
    span's (start, end) on ``perf_counter`` (``Window.t0``,
    ``Window.trace_end``), ``window`` the same span in the trace."""
    shift = window[0] - anchors[0]
    drift = abs((anchors[1] + shift) - window[1])
    if drift > ANCHOR_TOL_S:
        raise ValueError(f"the clocks disagree by {drift * 1e3:.3f} ms at "
                         "the end of the window")
    return [Event(s[0], s[1] + shift, s[2] + shift) for s in spans
            if s is not None]


def union_s(events: Iterable[Event], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] covered by at least one of ``events``."""
    return sum(e - s for s, e in trace_lib._union(
        [(e.start, e.end) for e in events], lo, hi))


def _innermost(events: Sequence[Event]) -> List[Tuple[float, float, str]]:
    """The timeline cut where any span starts or ends, each piece named by
    the latest-starting span that covers it."""
    marks = sorted({t for e in events for t in (e.start, e.end)})
    order = sorted(range(len(events)), key=lambda i: events[i].start)
    live: List[Tuple[float, int]] = []     # (-start, index): a max-heap
    out, k = [], 0
    for a, b in zip(marks, marks[1:]):
        while k < len(order) and events[order[k]].start <= a:
            i = order[k]
            heapq.heappush(live, (-events[i].start, i))
            k += 1
        # a span that ended under a later-starting one leaves once on top
        while live and events[live[0][1]].end <= a:
            heapq.heappop(live)
        if live:
            out.append((a, b, events[live[0][1]].name))
    return out


def idle_by_program_span(trace: Trace, events: Sequence[Event],
                         device: int = 0) -> Dict[str, float]:
    """Idle seconds on ``device`` inside the traced window, by the
    innermost span of ``events`` that covers them (``NO_SPAN`` where
    none does)."""
    lo, hi = trace.window
    idle, t = [], lo
    for s, e in trace_lib.busy_intervals(trace, device):
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if hi > t:
        idle.append((t, hi))
    named = _innermost(events)
    by: Dict[str, float] = {}
    j = 0
    for s, e in idle:
        covered = 0.0
        while j < len(named) and named[j][1] <= s:
            j += 1
        k = j
        while k < len(named) and named[k][0] < e:
            a, b, name = named[k]
            d = min(b, e) - max(a, s)
            if d > 0:
                by[name] = by.get(name, 0.0) + d
                covered += d
            k += 1
        if e - s > covered:
            by[NO_SPAN] = by.get(NO_SPAN, 0.0) + (e - s - covered)
    return by


def self_times(events: Sequence[Event], lo: float,
               hi: float) -> Dict[str, float]:
    """Seconds of [lo, hi] by the innermost span that covers them: each
    span's self time (its length less what its children cover)."""
    by: Dict[str, float] = {}
    for a, b, name in _innermost(events):
        d = min(b, hi) - max(a, lo)
        if d > 0:
            by[name] = by.get(name, 0.0) + d
    return by


def of_reading(r) -> Optional[List[Event]]:
    """:func:`host_events` of a traced run's reading (``bench/run.py``
    ``per_layer``), read once and kept on it."""
    if not hasattr(r, "program_events"):
        ctx = r.job.ctx
        path = trace_lib.find_xplane(os.path.join(
            ROOT, ".bench", "trace", f"{ctx.name}.{ctx.seed}"))
        r.program_events = host_events(path)
    return r.program_events
