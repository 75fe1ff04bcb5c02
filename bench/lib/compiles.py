"""Counts of tracing, compilation and persistent-cache traffic, from JAX's
monitoring events, so that a run can show what happened inside its
window (there should be no compilation there)."""
from __future__ import annotations

from typing import Dict

import jax

_DURATIONS = {"/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/core/compile/jaxpr_trace_duration": "traces"}
_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}


class Counter:
    def __init__(self):
        self.n: Dict[str, int] = {k: 0 for k in
                                  (*_DURATIONS.values(), *_EVENTS.values())}
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event: str, duration: float, **kw) -> None:
        key = _DURATIONS.get(event)
        if key is not None:
            self.n[key] += 1
            if key == "compiles":
                self.compile_s += duration

    def _ev(self, event: str, **kw) -> None:
        key = _EVENTS.get(event)
        if key is not None:
            self.n[key] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.n)

    def since(self, snap: Dict[str, int]) -> Dict[str, int]:
        return {k: v - snap[k] for k, v in self.n.items()}
