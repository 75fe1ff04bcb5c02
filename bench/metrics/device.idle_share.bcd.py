"""Share of the traced window in which no operation ran on the device."""
from bench.lib import trace


def read(r):
    return 100.0 * trace.idle_share(r.trace)
