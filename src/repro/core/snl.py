"""SNL — Selective Network Linearization (Cho et al., ICML 2022).

The paper's main baseline AND the recommended starting point for BCD
(B_ref checkpoints).  Learns real-valued per-site mask parameters α jointly
with θ under  CE + λ·||α||₁  (the L1 relaxation of Eq. 1), with the λ←κ·λ
correction schedule the paper's appendix analyzes, then hard-thresholds to the
target budget and finetunes — reproducing the "threshold cliff" that motivates
BCD.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.training import optimizer as opt_lib
from . import masks as M
from . import tracing


@dataclasses.dataclass
class SNLConfig:
    b_target: int
    lam0: float = 1e-4            # initial lasso coefficient λ₀
    kappa: float = 1.2            # λ ← κ·λ when sparsification stalls
    stall_delta: int = 0          # "stalled" = fewer ReLUs dropped than this
    alpha_threshold: float = 1e-2  # binarization threshold for budget counting
    epochs: int = 30
    steps_per_epoch: int = 20
    lr: float = 1e-3
    finetune_steps: int = 100
    seed: int = 0


@dataclasses.dataclass
class SNLResult:
    params: object
    masks: M.MaskTree             # hard binary masks at exactly b_target
    alphas: Dict[str, np.ndarray]  # final soft masks (pre-threshold)
    snapshots: List[M.MaskTree]   # binarized masks per epoch (Fig. 6 analysis)
    budget_per_epoch: List[int]
    lam_per_epoch: List[float]

    def stage_init(self) -> dict:
        """This result as a BCD warm-start (the paper's B_ref checkpoint),
        in the shared stage-init layout ``core.runner.save_stage_init``
        persists: SNL and AutoReP emit the same {kind, masks, params, aux}
        shape, so a budget sweep can descend from either."""
        return {"kind": "snl", "masks": self.masks, "params": self.params,
                "aux": {"alphas": self.alphas}}


def run_snl(
    params,
    alphas: Dict[str, jnp.ndarray],
    loss_fn: Callable,            # (params, alphas, batch, soft) -> (loss, acc)
    batches: Callable[[int], object],   # step -> batch
    cfg: SNLConfig,
    *,
    verbose: bool = False,
) -> SNLResult:
    opt = opt_lib.sgd(lr=cfg.lr, momentum=0.9,
                      schedule=opt_lib.cosine(cfg.lr, cfg.epochs *
                                              cfg.steps_per_epoch))

    def train_loss(both, batch, lam):
        p, a = both
        loss, _acc = loss_fn(p, a, batch, True)
        l1 = sum(jnp.sum(jnp.abs(v)) for v in a.values())
        return loss + lam * l1

    @jax.jit
    def step(both, ostate, batch, lam):
        grads = jax.grad(train_loss)(both, batch, lam)
        updates, ostate = opt.update(grads, ostate, both)
        p, a = opt_lib.apply_updates(both, updates)
        a = {k: jnp.clip(v, 0.0, 1.0) for k, v in a.items()}
        return (p, a), ostate

    both = (params, {k: jnp.asarray(v) for k, v in alphas.items()})
    ostate = opt.init(both)
    lam = cfg.lam0
    snapshots, budgets, lams = [], [], []
    prev_budget = None
    it = 0
    for epoch in range(cfg.epochs):
        for _ in range(cfg.steps_per_epoch):
            both, ostate = step(both, ostate, batches(it), lam)
            it += 1
        a_host = {k: np.asarray(v) for k, v in both[1].items()}
        hard = {k: (v > cfg.alpha_threshold).astype(np.float32)
                for k, v in a_host.items()}
        budget = M.count(hard)
        snapshots.append(hard)
        budgets.append(budget)
        lams.append(lam)
        if verbose:
            print(f"[snl] epoch={epoch} budget={budget} lam={lam:.2e}")
        if budget <= cfg.b_target:
            break
        if prev_budget is not None and prev_budget - budget <= cfg.stall_delta:
            lam *= cfg.kappa          # the κ correction mechanism
        prev_budget = budget

    # Hard threshold to EXACTLY b_target (the step that costs accuracy).
    a_host = {k: np.asarray(v) for k, v in both[1].items()}
    hard = M.threshold(a_host, cfg.b_target)

    # Finetune θ with binarized masks.
    params = finetune(both[0], hard, loss_fn, batches,
                      steps=cfg.finetune_steps, lr=cfg.lr, start_step=it)
    return SNLResult(params, hard, a_host, snapshots, budgets, lams)


def finetune(params, hard_masks: M.MaskTree, loss_fn, batches,
             *, steps: int, lr: float = 1e-3, start_step: int = 0,
             use_adam: bool = False):
    """Finetune θ under fixed binary masks (shared by SNL / BCD / AutoReP)."""
    with tracing.span("snl.finetune"):
        opt = (opt_lib.adamw(lr=lr, schedule=opt_lib.cosine(lr, steps))
               if use_adam else
               opt_lib.sgd(lr=lr, momentum=0.9,
                           schedule=opt_lib.cosine(lr, steps)))
        masks_dev = M.as_device(hard_masks)

        # masks are a jit argument, not a closure constant: every call
        # then lowers the same program, which the persistent compilation
        # cache serves instead of recompiling per BCD step
        @jax.jit
        def step(p, ostate, batch, masks):
            def l(p):
                loss, _ = loss_fn(p, masks, batch, False)
                return loss
            grads = jax.grad(l)(p)
            updates, ostate = opt.update(grads, ostate, p)
            return opt_lib.apply_updates(p, updates), ostate

        ostate = opt.init(params)
        for i in range(steps):
            params, ostate = step(params, ostate, batches(start_step + i),
                                  masks_dev)
        return params
