"""Whole-step share of the chip's bf16 peak: the FLOPs a BCD outer step
requires, over the traced window.

Required FLOPs per step (``bench/lib/flops.py``, from the configuration's
shapes): each candidate's forward from the segment of its earliest edited
site to the head over the eval batch (a prefix served from a cache is not
counted again); two full forwards of the eval batch (base and
post-finetune evaluation); and per finetune step 3 forwards' worth of the
train batch (forward and backward)."""
from bench.lib import flops


def read(r):
    if not r.steps:
        return None
    c = r.config
    fwd = flops.cnn_forward(c)
    per_step = 2 * c["eval_batch"] * fwd \
        + c["finetune_steps"] * 3 * c["finetune_batch"] * fwd
    total = sum(s["flops"] for s in r.steps) + len(r.steps) * per_step
    return 100.0 * total / (r.window_s * r.chips * r.peaks.bf16_flops)
