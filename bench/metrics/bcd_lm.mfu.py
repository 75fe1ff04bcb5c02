"""Whole-step share of the chip's bf16 peak in the LM cells: the FLOPs a
BCD outer step requires, over the traced window, counted as ``bcd.mfu``
counts them (``bench/lib/lm_flops.py``): each candidate's forward from the
layer of its earliest edit to the head over the eval batch (a cached
prefix is not counted again), two full forwards of the eval batch, and 3
forwards' worth of the finetune batch per finetune step.  The held
experts' work follows the rows the program routed to them under the step's
base masks (``moe.rows_held``), or uniform routing where the job read
none."""
from bench.lib import lm_flops


def read(r):
    if not r.steps:
        return None
    c = r.config
    seq, n_eval = c["eval_len"], c["eval_batch"] * c["eval_len"]
    n_ft = c["finetune_batch"] * c["finetune_len"]
    total = 0.0
    for s in r.steps:
        rows = (s["rows"].sum(axis=1) / n_eval).tolist() if "rows" in s \
            else None
        suffix = lm_flops.suffix_token(c, seq, rows)
        total += n_eval * sum(suffix[cut] for cut in s["cands"])
        total += 2 * n_eval * lm_flops.forward_token(c, seq, rows)
        total += c["finetune_steps"] * 3 * n_ft * lm_flops.forward_token(
            c, c["finetune_len"], rows)
    return 100.0 * total / (r.window_s * r.chips * r.peaks.bf16_flops)
