"""Device seconds per traced BCD outer step in the held experts: the
operations under the named scope ``moe.experts`` (sorting the routed rows
by expert, the three grouped matmuls and the masked gate; forward and the
finetune's backward; ``bench/lib/scopes.py``)."""
from bench.lib import scopes


def read(r):
    keys = scopes.of_reading(r)
    if keys is None or not r.steps:
        return None
    s = scopes.scope_s(r.trace, keys, "moe.experts")
    return s / len(r.steps) if s > 0 else None
