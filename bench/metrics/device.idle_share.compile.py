"""Share of the traced window in which device 0 ran nothing while the host
was inside one of JAX's compile phases (``jax.trace``, ``jax.lower``,
``jax.compile``): each idle stretch is put down to the innermost program
or compile-phase span that covers it
(``program_trace.idle_by_program_span``), and the compile phases' part is
summed."""
from bench.lib import program_trace


def read(r):
    events = program_trace.of_reading(r)
    if events is None:
        return None
    idle = program_trace.idle_by_program_span(r.trace, events)
    s = sum(v for k, v in idle.items() if program_trace.is_jax(k))
    return 100.0 * s / r.trace.window_s
