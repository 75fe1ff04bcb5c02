"""The held experts' share of their roofline: the least time the chip
could take for their grouped matmuls in the traced steps (the larger of
their FLOPs over the bf16 peak and their least bytes over the HBM
bandwidth, ``bench/lib/lm_flops.experts_call``), over the device time under
the ``moe.experts`` scope.

Per traced step the grouped matmuls run once per expert layer after each
chunk's cut for every candidate chunk (its padded size times the eval
batch's rows), once per expert layer for each of the two evaluations, and
per finetune step once forward and twice in the backward (input and weight
gradients, counted as two forward calls).  The rows are those the program
routed to the held experts under the step's base masks
(``moe.rows_held``, read after the window)."""
from bench.lib import lm_flops, scopes


def read(r):
    keys = scopes.of_reading(r)
    if keys is None or not r.steps or any("rows" not in s for s in r.steps):
        return None
    t = scopes.scope_s(r.trace, keys, "moe.experts")
    if t <= 0:
        return None
    c = r.config
    n_eval = c["eval_batch"] * c["eval_len"]
    n_ft = c["finetune_batch"] * c["finetune_len"]
    L0 = c["first_k_dense_replace"]
    flops = nbytes = 0.0

    def add(rows, times):
        nonlocal flops, nbytes
        f, b = lm_flops.experts_call(c, rows)
        flops += times * f
        nbytes += times * b
    for s in r.steps:
        rpt = s["rows"].sum(axis=1) / n_eval
        for cut, size in s["chunks"]:
            for j in range(max(int(cut[len("layer"):]) - L0, 0), len(rpt)):
                add(size * n_eval * rpt[j], 1)
        for j in range(len(rpt)):
            add(n_eval * rpt[j], 2)
            add(n_ft * rpt[j], 3 * c["finetune_steps"])
    least = max(flops / r.peaks.bf16_flops, nbytes / r.peaks.hbm_bytes)
    return 100.0 * least / t
