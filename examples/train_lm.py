"""End-to-end LM training driver: data -> sharded train loop -> checkpoints ->
fault-tolerant supervisor -> BCD linearization of the trained model.

    PYTHONPATH=src python examples/train_lm.py                 # ~2M params
    PYTHONPATH=src python examples/train_lm.py --dim 768 --layers 12 \
        --steps 300                                            # ~100M params

Runs on whatever devices exist (CPU here; the same code path drives the
production mesh via --mesh data,model).  Demonstrates: Markov-token pipeline,
AdamW + cosine, remat, checkpoint/restart with injected failure, straggler
watchdog, and a final BCD pass that removes 50% of FFN nonlinearities.
"""
import argparse
import dataclasses
import os
import shutil

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import bcd, engine, linearize, masks as M
from repro.data import MarkovTokens
from repro.models.lm import LM
from repro.training import checkpoint, ft
from repro.training import optimizer as opt_lib, train as train_lib


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_1p6b")
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--inject-failure", type=int, default=25,
                    help="simulate a node failure at this step (-1 = off)")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    cfg = dataclasses.replace(
        cfg, n_layers=args.layers, d_model=args.dim,
        n_heads=max(4, args.dim // 32), n_kv_heads=max(2, args.dim // 64),
        head_dim=32, d_ff=args.dim * 3, vocab=args.vocab, dtype="float32")
    model = LM(cfg)
    nparams = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))))
    print(f"arch={cfg.name} params={nparams/1e6:.1f}M")

    mt = MarkovTokens(cfg.vocab, seed=0)
    opt = opt_lib.adamw(lr=3e-3, grad_clip=1.0,
                        schedule=opt_lib.cosine(3e-3, args.steps))
    train_step = jax.jit(train_lib.make_train_step(
        model, opt, train_lib.TrainStepCfg(remat=True, dp_axes=())),
        donate_argnums=(0,))
    masks = M.as_device(linearize.init_masks(model.mask_sites()))

    losses = []

    def init_state():
        return train_lib.make_state(model, opt, jax.random.PRNGKey(1))

    def step_fn(state, step):
        b = {k: jnp.asarray(v)
             for k, v in mt.batch(args.batch, args.seq, step).items()}
        state, metrics = train_step(state, b, masks)
        losses.append(float(metrics["loss"]))
        if step % 10 == 0:
            print(f"step {step:4d} loss {losses[-1]:.3f}")
        return state

    shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    injector = ft.FailureInjector(
        fail_at_steps=(args.inject_failure,) if args.inject_failure >= 0
        else ())
    watchdog = ft.StragglerWatchdog()
    out = ft.run_supervised(init_state, step_fn, n_steps=args.steps,
                            ckpt_dir=args.ckpt_dir, ckpt_every=10,
                            injector=injector, watchdog=watchdog)
    print(f"done: restarts={out['restarts']} "
          f"flagged_straggler_steps={out['flagged_steps']}")
    print(f"loss {losses[0]:.3f} -> {np.mean(losses[-5:]):.3f}")

    # ---- linearize the trained model with BCD ------------------------
    state = out["state"]
    eval_b = {k: jnp.asarray(v)
              for k, v in mt.batch(16, args.seq, 10**6).items()}

    def token_acc_fn(m):
        logits, _ = model.forward(state["params"], m, eval_b["tokens"])
        return jnp.mean((jnp.argmax(logits, -1) == eval_b["labels"])
                        .astype(jnp.float32)) * 100

    token_acc = jax.jit(token_acc_fn)
    masks_h = linearize.init_masks(model.mask_sites())
    total = M.count(masks_h)
    # Candidate trials go through the full-forward pipelined evaluator: one
    # jitted call per chunk of candidate mask trees (masks ride the scanned
    # stack as jit inputs — no recompilation across candidates).
    res = bcd.run_bcd(
        masks_h,
        bcd.BCDConfig(b_target=total // 2, drc=max(1, total // 10), rt=4,
                      adt=0.5, finetune_every_step=False, chunk_size=4),
        lambda m: float(token_acc(M.as_device(m))),
        evaluator=engine.PipelinedEvaluator(token_acc_fn, pad_to=4),
        verbose=True)
    print(f"BCD: kept {M.count(res.masks)}/{total} FFN nonlinearities; "
          f"token acc {float(token_acc(M.as_device(res.masks))):.1f}%")


if __name__ == "__main__":
    main()
