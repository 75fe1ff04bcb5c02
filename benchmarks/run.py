"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME]

Output: ``name,us_per_call,derived`` CSV rows (derived = the table's metric).
All paper tables are accuracy-vs-budget pipelines; offline they run the same
algorithms at reduced scale on synthetic CIFAR (EXPERIMENTS.md documents the
mapping; absolute accuracies differ from the paper, relative claims hold).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks import common as C
from repro.core import analysis, autorep, linearize, masks as M, pi_cost


def bench_table23_bcd_vs_snl():
    """Tables 2 & 3 / Fig. 1: accuracy vs ReLU budget, SNL vs SNL+BCD."""
    model, data, params, loss_fn, batches, masks0 = C.trained_pipeline()
    sloss = C.soft_loss_fn(model)
    total = M.count(masks0)
    for frac in (0.25, 0.1):
        b_target = int(total * frac)
        b_ref = int(total * (frac + 0.15))
        t0 = time.perf_counter()
        res_ref = C.run_snl_to(model, params, sloss, batches, masks0, b_ref)
        res_snl = C.run_snl_to(model, params, sloss, batches, masks0,
                               b_target)
        acc_snl = C.test_acc(model, res_snl.params, res_snl.masks, data)
        holder = {"params": res_ref.params}
        res_bcd = C.run_bcd_from(model, data, holder, sloss, batches,
                                 res_ref.masks, b_target)
        acc_bcd = C.test_acc(model, holder["params"], res_bcd.masks, data)
        us = (time.perf_counter() - t0) * 1e6
        C.row(f"table23.budget={b_target}", us,
              f"snl_acc={acc_snl:.1f};bcd_acc={acc_bcd:.1f};"
              f"budget_exact={M.count(res_bcd.masks) == b_target}")


def bench_fig4_bcd_on_autorep():
    """Fig. 4: BCD on top of AutoReP (poly2 replacement)."""
    model, data, params, loss_fn, batches, masks0 = C.trained_pipeline(seed=1)
    sites = {k: linearize.MaskSite(s.shape, "relu", "poly2")
             for k, s in model.mask_sites().items()}
    alphas = {k: jnp.full(s.shape, 0.5) for k, s in sites.items()}
    poly = linearize.init_poly(sites)
    total = M.count(masks0)
    b_ref, b_target = int(total * 0.35), int(total * 0.15)

    def loss3(p, m, q, batch, soft):
        logits = model.forward(p, m, batch["images"], poly=q, soft=soft)
        from repro.training.train import cross_entropy
        return cross_entropy(logits, batch["labels"]), 0.0

    t0 = time.perf_counter()
    res_ar = autorep.run_autorep(
        params, alphas, poly, loss3, batches,
        autorep.AutoRepConfig(b_target=b_ref, epochs=4, steps_per_epoch=5,
                              lr=3e-2, finetune_steps=10))
    acc_ar = C.test_acc(model, res_ar.params, res_ar.masks, data)
    holder = {"params": res_ar.params}
    sloss = C.soft_loss_fn(model)
    res_bcd = C.run_bcd_from(model, data, holder, sloss, batches,
                             res_ar.masks, b_target)
    acc_bcd = C.test_acc(model, holder["params"], res_bcd.masks, data)
    us = (time.perf_counter() - t0) * 1e6
    C.row("fig4.autorep+bcd", us,
          f"autorep@{b_ref}={acc_ar:.1f};bcd@{b_target}={acc_bcd:.1f}")


def bench_fig5_ablations():
    """Fig. 5: DRC / finetune-epochs / ADT ablations."""
    model, data, params, loss_fn, batches, masks0 = C.trained_pipeline(seed=2)
    sloss = C.soft_loss_fn(model)
    total = M.count(masks0)
    b_ref, b_target = int(total * 0.35), int(total * 0.15)
    res_ref = C.run_snl_to(model, params, sloss, batches, masks0, b_ref)
    for drc_frac, name in ((0.05, "small"), (0.25, "large")):
        drc = max(1, int((b_ref - b_target) * drc_frac))
        holder = {"params": res_ref.params}
        t0 = time.perf_counter()
        res = C.run_bcd_from(model, data, holder, sloss, batches,
                             res_ref.masks, b_target, drc=drc)
        acc = C.test_acc(model, holder["params"], res.masks, data)
        C.row(f"fig5a.drc_{name}", (time.perf_counter() - t0) * 1e6,
              f"drc={drc};acc={acc:.1f};steps={len(res.history)}")
    for ft_steps in (2, 12):
        holder = {"params": res_ref.params}
        t0 = time.perf_counter()
        res = C.run_bcd_from(model, data, holder, sloss, batches,
                             res_ref.masks, b_target, ft_steps=ft_steps)
        acc = C.test_acc(model, holder["params"], res.masks, data)
        C.row(f"fig5b.ft={ft_steps}", (time.perf_counter() - t0) * 1e6,
              f"acc={acc:.1f}")
    for adt in (0.1, 1.0):
        holder = {"params": res_ref.params}
        t0 = time.perf_counter()
        res = C.run_bcd_from(model, data, holder, sloss, batches,
                             res_ref.masks, b_target, adt=adt)
        acc = C.test_acc(model, holder["params"], res.masks, data)
        trials = sum(h.trials for h in res.history)
        C.row(f"fig5c.adt={adt}", (time.perf_counter() - t0) * 1e6,
              f"acc={acc:.1f};total_trials={trials}")


def bench_fig6_mask_iou():
    """Fig. 6: IoU of masks along an SNL optimization path (> 0.85)."""
    model, data, params, loss_fn, batches, masks0 = C.trained_pipeline(seed=3)
    sloss = C.soft_loss_fn(model)
    total = M.count(masks0)
    t0 = time.perf_counter()
    res = C.run_snl_to(model, params, sloss, batches, masks0,
                       int(total * 0.4), epochs=8)
    snaps = [s for s in res.snapshots if M.count(s) > 0]
    ious = analysis.consecutive_iou(snaps)
    frac = analysis.golden_set_fraction(snaps)
    C.row("fig6.snl_iou", (time.perf_counter() - t0) * 1e6,
          f"min_consec_iou={min(ious):.3f};frac_pairs_gt_0.85={frac:.2f}")


def bench_fig7_relu_distribution():
    """Fig. 7: per-layer ReLU distribution of the BCD result."""
    model, data, params, loss_fn, batches, masks0 = C.trained_pipeline(seed=4)
    sloss = C.soft_loss_fn(model)
    total = M.count(masks0)
    holder = {"params": params}
    t0 = time.perf_counter()
    res = C.run_bcd_from(model, data, holder, sloss, batches, masks0,
                         int(total * 0.5))
    dist = analysis.layer_distribution(res.masks)
    kept = ";".join(f"{k}={a}/{b}" for k, (a, b) in list(dist.items())[:4])
    C.row("fig7.distribution", (time.perf_counter() - t0) * 1e6, kept)


def bench_table1_relu_counts():
    """Table 1: total ReLUs per backbone × image size."""
    from repro.models.resnet import CNN, CNNConfig
    t0 = time.perf_counter()
    vals = {}
    for name, mk, sz in (("resnet18", CNNConfig.resnet18, 32),
                         ("resnet18", CNNConfig.resnet18, 64),
                         ("wrn22_8", CNNConfig.wrn22_8, 32),
                         ("wrn22_8", CNNConfig.wrn22_8, 64)):
        vals[f"{name}@{sz}"] = CNN(mk(10, sz)).relu_count()
    C.row("table1.relu_counts", (time.perf_counter() - t0) * 1e6,
          ";".join(f"{k}={v}" for k, v in vals.items()))


def bench_pi_latency():
    """Intro claim: PI latency scales with ReLU count (DELPHI cost model)."""
    t0 = time.perf_counter()
    parts = []
    for budget in (570_000, 100_000, 15_000, 6_000):
        c = pi_cost.cost(budget, 17)
        parts.append(f"B={budget}:lat={c.online_latency_s:.2f}s")
    C.row("pi.latency_model", (time.perf_counter() - t0) * 1e6,
          ";".join(parts))


def bench_kernel_masked_act():
    """Kernel microbench: fused masked activation (jnp path timing on CPU;
    the Pallas path is validated in interpret mode in tests)."""
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4096, 4096)).astype(np.float32))
    m = jnp.asarray((rng.random(4096) > 0.5).astype(np.float32))
    f = jax.jit(lambda x, m: ops.masked_act(x, m, kind="relu"))
    f(x, m).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        f(x, m).block_until_ready()
    us = (time.perf_counter() - t0) / 10 * 1e6
    gb = x.size * 4 * 2 / 1e9
    C.row("kernel.masked_act", us, f"GBps={gb / (us / 1e6):.1f}")


def bench_lm_linearize():
    """Beyond-paper: BCD linearization of a reduced LM (FFN channel masks)."""
    from repro.configs import get_config
    from repro.core import bcd
    from repro.models.lm import LM
    from repro.data import MarkovTokens
    from repro.training import optimizer as opt_lib, train as train_lib
    cfg = get_config("stablelm_1p6b").reduced()
    model = LM(cfg)
    mt = MarkovTokens(cfg.vocab, seed=0)
    opt = opt_lib.adamw(lr=2e-3)
    step = jax.jit(train_lib.make_train_step(
        model, opt, train_lib.TrainStepCfg(remat=False, dp_axes=())))
    state = train_lib.make_state(model, opt, jax.random.PRNGKey(1))
    masks0 = linearize.init_masks(model.mask_sites())
    mdev = M.as_device(masks0)
    for i in range(30):
        b = {k: jnp.asarray(v) for k, v in mt.batch(8, 64, i).items()}
        state, metrics = step(state, b, mdev)
    eval_b = {k: jnp.asarray(v) for k, v in mt.batch(16, 64, 999).items()}

    @jax.jit
    def acc(masks):
        logits, _ = model.forward(state["params"], masks, eval_b["tokens"])
        return jnp.mean((jnp.argmax(logits, -1) == eval_b["labels"])
                        .astype(jnp.float32)) * 100
    total = M.count(masks0)
    t0 = time.perf_counter()
    res = bcd.run_bcd(
        masks0, bcd.BCDConfig(b_target=total // 2, drc=total // 8, rt=4,
                              adt=0.5, finetune_every_step=False),
        lambda m: float(acc(M.as_device(m))))
    a = float(acc(M.as_device(res.masks)))
    C.row("lm.bcd_linearize", (time.perf_counter() - t0) * 1e6,
          f"budget={M.count(res.masks)}/{total};token_acc={a:.1f}")


ALL = [bench_table1_relu_counts, bench_pi_latency, bench_kernel_masked_act,
       bench_fig6_mask_iou, bench_fig7_relu_distribution,
       bench_fig5_ablations, bench_table23_bcd_vs_snl,
       bench_fig4_bcd_on_autorep, bench_lm_linearize]


def main() -> int:
    """Run every selected phase; a phase that raises prints an ``ERROR``
    row and its traceback, the rest still run, and the exit code is 1."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    failed = []
    for fn in ALL:
        if args.only and args.only not in fn.__name__:
            continue
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — report, run the rest, fail
            failed.append(fn.__name__)
            C.row(fn.__name__, 0.0, f"ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        print(f"FAILED phases: {','.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
