"""Device time by the program's ``jax.named_scope``.

A profiler trace names a device operation by its HLO text alone
(``%fusion.26 = f32[...] fusion(...), kind=..., calls=...``), without the
metadata that carries the named scope.  The compiled programs' HLO text
(``jax.stages.Compiled.as_text()``) has both: every instruction line with
its ``metadata={op_name="jit(f)/…/mla/dot_general" …}``.  :func:`op_scopes`
keys each instruction by its name, result shape and opcode, and gives it
the innermost of the wanted scopes that its ``op_name`` passes through
(backward passes keep the forward's scope names); :func:`scope_s` sums the
device time of the trace's operations under one scope.  A key met with
two different scopes in the programs given is left out.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, Optional, Tuple

from . import trace as trace_lib

#: the program's named scopes that the per-layer metrics read
SCOPES = ("mla", "moe.route", "moe.experts", "moe.shared")
_INSTR = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
Key = Tuple[str, str, str]


def _key(m) -> Key:
    return m.group(1), m.group(2), m.group(3)


def scope_in(op_name: str, scopes: Iterable[str]) -> Optional[str]:
    """The innermost of ``scopes`` that ``op_name``'s path passes through."""
    parts = re.split(r"[/()]", op_name)
    hit = None
    for p in parts:
        if p in scopes:
            hit = p
    return hit


def op_scopes(hlo_texts: Iterable[str], scopes: Iterable[str]
              ) -> Dict[Key, str]:
    scopes = set(scopes)
    out: Dict[Key, Optional[str]] = {}
    for text in hlo_texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            if m is None:
                continue
            name = _OP_NAME.search(line)
            scope = scope_in(name.group(1), scopes) if name else None
            k = _key(m)
            if k in out and out[k] != scope:
                out[k] = None
            else:
                out.setdefault(k, scope)
    return {k: v for k, v in out.items() if v is not None}


def scope_s(trace: trace_lib.Trace, keys: Dict[Key, str],
            scope: str) -> float:
    """Device seconds in the traced window of the operations under
    ``scope``, summed per device and averaged over the devices."""
    total = []
    for ops in trace.device_ops:
        s = 0.0
        for e in ops:
            m = _INSTR.match(e.name)
            if m is not None and keys.get(_key(m)) == scope:
                s += trace_lib._in_window(trace, e)
        total.append(s)
    return sum(total) / max(len(total), 1)


def of_reading(r) -> Optional[Dict[Key, str]]:
    """:func:`op_scopes` of the HLO text that a traced run's job read
    (``hlo_texts``), kept on the reading; ``None`` where the job read
    none."""
    if not hasattr(r, "op_scopes"):
        texts = getattr(r.job, "hlo_texts", None)
        r.op_scopes = None if not texts else op_scopes(texts, SCOPES)
    return r.op_scopes
