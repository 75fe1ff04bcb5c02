"""Host seconds per BCD outer step inside the candidate engine: every call
that ``bcd_steps`` makes into the evaluator (begin_step, stage, evaluate)
and the context swap after finetuning, from the benchmark's host spans."""


def read(r):
    if not r.steps:
        return None
    return sum(s["engine_s"] for s in r.steps) / len(r.steps)
