"""Host seconds per traced BCD outer step in JAX's compile phases: the
length of the union of the ``jax.trace``, ``jax.lower`` and ``jax.compile``
events in the traced window (tracing to a jaxpr, lowering to MLIR, XLA
compilation or the load from the persistent cache;
``bench/lib/program_trace.py``), over the traced steps.  Nested phases
(the trace of a jit inside the trace of its caller) count once."""
from bench.lib import program_trace


def read(r):
    events = program_trace.of_reading(r)
    if events is None or not r.steps:
        return None
    lo, hi = r.trace.window
    jax_events = [e for e in events if program_trace.is_jax(e.name)]
    return program_trace.union_s(jax_events, lo, hi) / len(r.steps)
