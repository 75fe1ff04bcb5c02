"""Share of the candidates whose chunk took the suffix path (a cut site,
not the full-forward fallback), counted by the benchmark's evaluator
wrapper from the chunks the engine planned."""


def read(r):
    n = sum(s["n_cand"] for s in r.steps)
    if not n:
        return None
    return 100.0 * sum(s["n_suffix"] for s in r.steps) / n
