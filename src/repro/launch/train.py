"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm_1p6b \
        --reduced --steps 20 --mesh 1,1 --ckpt-dir /tmp/ck

On a real cluster this binary runs once per host (jax.distributed.initialize
picks up the pod topology); here --mesh data,model builds the mesh over local
devices.  Uses the same jit_train_step the dry-run proves out, under the
fault-tolerance supervisor with checkpointing.
"""
from __future__ import annotations

import argparse
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import linearize, masks as M
from repro.data import MarkovTokens, host_slice
from repro.models.lm import LM
from repro.training import ft
from repro.training import optimizer as opt_lib, train as train_lib
from .mesh import dp_axes as mesh_dp_axes, make_host_mesh


def main(argv=None):
    """CLI entry: supervised, checkpointed training over a local mesh."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_1p6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="1,1", help="data,model axis sizes")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--remat-group", type=int, default=1)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, remat_group=args.remat_group)
    d, m = (int(x) for x in args.mesh.split(","))
    mesh = make_host_mesh(d, m)
    model = LM(cfg)
    opt = opt_lib.adamw(lr=args.lr, grad_clip=1.0,
                        schedule=opt_lib.cosine(args.lr, args.steps))
    tcfg = train_lib.TrainStepCfg(remat=True, dp_axes=("data",),
                                  compress_grads=args.compress_grads)
    mt = MarkovTokens(cfg.vocab, seed=0)
    masks = M.as_device(linearize.init_masks(model.mask_sites()))
    sl = host_slice(args.global_batch)

    with jax.set_mesh(mesh):
        step_jit = train_lib.jit_train_step(model, opt, mesh, tcfg)

        def init_state():
            return train_lib.make_state(model, opt, jax.random.PRNGKey(0))

        losses = []

        def step_fn(state, i):
            b = mt.batch(args.global_batch, args.seq, i)
            b = {k: jnp.asarray(v[sl]) for k, v in b.items()}
            state, metrics = step_jit(state, b, masks)
            losses.append(float(metrics["loss"]))
            print(f"step {i} loss {losses[-1]:.4f}")
            return state

        out = ft.run_supervised(init_state, step_fn, n_steps=args.steps,
                                ckpt_dir=args.ckpt_dir,
                                ckpt_every=args.ckpt_every,
                                watchdog=ft.StragglerWatchdog())
    print(f"finished {out['completed_steps']} steps; "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}; "
          f"restarts={out['restarts']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
