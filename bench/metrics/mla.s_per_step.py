"""Device seconds per traced BCD outer step in the program's latent
attention: the operations that the compiled programs' HLO metadata puts
under the named scope ``mla`` (``bench/lib/scopes.py``), in every program
of the step (candidates, evaluations, the finetune's forward and
backward)."""
from bench.lib import scopes


def read(r):
    keys = scopes.of_reading(r)
    if keys is None or not r.steps:
        return None
    s = scopes.scope_s(r.trace, keys, "mla")
    return s / len(r.steps) if s > 0 else None
