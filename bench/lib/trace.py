"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to the
  traced window and averaged over the devices;
* idle share: 1 - busy / window;
* kernel time: the summed device durations of the operations whose name
  matches a pattern, per device;
* top operations: device seconds by operation name;
* idle gaps: each gap between busy intervals, named by the innermost host
  span of the benchmark (``bench.<name>``) that covers its midpoint, and
  summed by that name.

The window is the benchmark's own ``bench.window`` host span, so host and
device events are read on the profiler's one clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

import numpy as np

from . import spans as spans_lib

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINES = ("XLA Ops", "XLA Modules")    # the first present is read
WINDOW = spans_lib.PREFIX + "window"


@dataclasses.dataclass
class Event:
    name: str
    start: float      # seconds on the profiler's clock
    end: float


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    device_ops: List[List[Event]]        # per device
    host_spans: List[Event]              # bench.* spans, prefix stripped

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read the device operations and the benchmark's host spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            name = next((n for n in OPS_LINES if n in lines), None)
            devices.append([] if name is None else [
                Event(e.name, e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9)
                for e in lines[name].events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(spans_lib.PREFIX):
                        host.append(Event(e.name[len(spans_lib.PREFIX):],
                                          e.start_ns * 1e-9,
                                          (e.start_ns + e.duration_ns)
                                          * 1e-9))
    windows = [e for e in host if e.name == "window"]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} window spans, not 1")
    if not devices or not any(devices):
        raise ValueError("trace holds no device operations; planes: "
                         f"{[p.name for p in data.planes]}")
    w = windows[0]
    return Trace((w.start, w.end), devices,
                 [e for e in host if e.name != "window"])


def _union(intervals: Sequence[Tuple[float, float]], lo: float,
           hi: float) -> List[Tuple[float, float]]:
    """Sorted disjoint union of intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_intervals(trace: Trace, device: int) -> List[Tuple[float, float]]:
    lo, hi = trace.window
    return _union([(e.start, e.end) for e in trace.device_ops[device]],
                  lo, hi)


def busy_s(trace: Trace) -> float:
    """Device-busy seconds in the window, averaged over the devices."""
    return float(np.mean([sum(e - s for s, e in busy_intervals(trace, d))
                          for d in range(len(trace.device_ops))]))


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / trace.window_s


def _in_window(trace: Trace, e: Event) -> float:
    lo, hi = trace.window
    return max(0.0, min(e.end, hi) - max(e.start, lo))


def kernel_s(trace: Trace, pattern: str) -> float:
    """Device seconds of operations whose name matches ``pattern``
    (``re.search``), summed per device and averaged over the devices."""
    rx = re.compile(pattern)
    return float(np.mean([sum(_in_window(trace, e) for e in ops
                              if rx.search(e.name))
                          for ops in trace.device_ops]))


_OP = re.compile(r"^(%[\w.\-]+) = .*?[\]})] ([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.26 fusion`` for the HLO text an ``XLA Ops`` event carries
    (Pallas calls keep their ``tpu_custom_call`` target)."""
    m = _OP.match(name)
    if m is None:
        return name[:80]
    out = f"{m.group(1)} {m.group(2)}"
    return out + " tpu_custom_call" if "tpu_custom_call" in name else out


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """``[[name, device seconds]]`` of the operations that took most time
    (summed over devices, then averaged), by their short name."""
    tot: Dict[str, float] = {}
    for ops in trace.device_ops:
        for e in ops:
            k = short_name(e.name)
            tot[k] = tot.get(k, 0.0) + _in_window(trace, e)
    k = len(trace.device_ops)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, s / k] for name, s in ranked]


def _host_at(spans: List[Event], starts: List[float], t: float) -> str:
    """The innermost (latest-starting) host span that covers ``t``;
    ``spans`` sorted by start, ``starts`` their start times."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        if spans[i].end > t:
            return spans[i].name
        i -= 1
    return "host (no bench span)"


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """``[[host span, idle seconds]]``: device-idle time in the window on
    device 0, summed by the host span that each gap fell in."""
    lo, hi = trace.window
    busy = busy_intervals(trace, 0)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    spans = sorted(trace.host_spans, key=lambda e: e.start)
    starts = [e.start for e in spans]
    by: Dict[str, float] = {}
    for s, e in gaps:
        name = _host_at(spans, starts, 0.5 * (s + e))
        by[name] = by.get(name, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]
