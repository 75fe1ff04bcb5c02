"""Host seconds per BCD outer step in the finetune callback
(``core.snl.finetune``, ended by ``block_until_ready``)."""


def read(r):
    if not r.steps:
        return None
    return sum(s["finetune_s"] for s in r.steps) / len(r.steps)
