"""The paper's full pipeline on ResNet18: train -> SNL(B_ref) -> BCD(B_target)
vs SNL(B_target) head-to-head (Fig. 1 / Table 3 protocol, synthetic CIFAR).

    PYTHONPATH=src python examples/resnet18_bcd_pipeline.py \
        [--image-size 16] [--ref-frac 0.6] [--target-frac 0.4] [--full] \
        [--engine suffix|sequential] [--chunk-size 8] \
        [--moves remove,add_back,swap,stage_drop,share] \
        [--proposal uniform|sensitivity]

--full uses the real ResNet18 geometry at 32x32 (slow on CPU); the default
uses a reduced stage plan with the same code path.  --engine selects the BCD
candidate evaluation (core.engine): 'suffix' (the default) groups candidate
chunks by the segment of their earliest mutated mask site, computes the
shared forward prefix once per site per step and vmaps only the suffix per
candidate; shallow cuts take the full-forward fallback, staged two chunks
ahead of the one being read (docs/bcd_engine.md).  'sequential' is the
reference, one forward per candidate.  Selection is bit-identical across
engines for a fixed seed.  --moves widens the
coordinate-descent move set beyond the paper's removals (docs/bcd_engine.md
§Move vocabulary) and --proposal sensitivity weights kinds/sites by their
running acceptance rates; per-kind accepted/proposed counters land in the
sweep artifact and print at exit.  JAX's persistent compilation cache is
on (launch.compile_cache: JAX_COMPILATION_CACHE_DIR, else
<checkout>/.jax_cache), so re-runs and resumed sweeps skip re-jit (hit
counts print at exit).

Sweep mode (the paper's accuracy-vs-budget curve, Fig. 4 protocol):

    PYTHONPATH=src python examples/resnet18_bcd_pipeline.py \
        --sweep 0.55,0.4 --out-dir runs/r18 [--engine suffix]

descends the budget schedule with warm-starting + finetuning between stages,
checkpointing after every accepted block (launch.sweep / core.runner).  The
run is fully restartable: kill it at any point — SIGKILL included — and
rerunning the same command resumes where it stopped, bit-identically; the
persisted SNL warm start under <out-dir>/init is reused, so a resume skips
training entirely.  The curve lands in <out-dir>/SWEEP_<model>.json.

--overlap starts stage i+1's BCD descent as soon as stage i's accepted
masks land, running stage i's reporting tail (per-stage finetune + test
scoring) concurrently on a worker thread — masks and step logs stay
bit-identical to a serial sweep; only wall-clock changes.

Multi-host: launch one process per rank with REPRO_COORD_RANK /
REPRO_COORD_WORLD / REPRO_COORD_DIR (shared path) / REPRO_COORD_SESSION
exported (launch.coordinator.from_env); rank 0 owns every checkpoint and
artifact, other ranks follow its lineage and verify they resumed the same
manifest fingerprint.  Unset, the run is plain single-process.
"""
import argparse
import os

import jax
import jax.numpy as jnp

from repro.core import bcd, linearize, masks as M, runner, tracing
from repro.core.snl import SNLConfig, finetune, run_snl
from repro.data import ImageDatasetCfg, SyntheticImages
from repro.launch import compile_cache
from repro.launch import coordinator as coord_lib
from repro.launch import sweep as sweep_lib
from repro.models.resnet import CNN, CNNConfig
from repro.training import optimizer as opt_lib, train as train_lib


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--image-size", type=int, default=16)
    ap.add_argument("--ref-frac", type=float, default=0.6)
    ap.add_argument("--target-frac", type=float, default=0.4)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--engine", default="suffix",
                    choices=["sequential", "suffix"])
    ap.add_argument("--chunk-size", type=int, default=8)
    ap.add_argument("--moves", default="remove",
                    help="comma-separated move kinds the descent samples "
                         f"from (subset of {','.join(M.MOVE_KINDS)}); "
                         "'remove' alone replays the paper's Alg. 2 "
                         "bit-identically")
    ap.add_argument("--proposal", default="uniform",
                    choices=list(M.PROPOSALS),
                    help="candidate proposal distribution: 'uniform', or "
                         "'sensitivity' to weight kinds/sites by their "
                         "running acceptance rates")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated descending budget fractions "
                         "(e.g. '0.55,0.4'): run the multi-budget sweep "
                         "driver instead of the single head-to-head")
    ap.add_argument("--out-dir", default=None,
                    help="sweep output/checkpoint directory (required with "
                         "--sweep)")
    ap.add_argument("--overlap", action="store_true",
                    help="overlap each sweep stage's reporting tail "
                         "(finetune + test scoring) with the next stage's "
                         "BCD descent; masks stay bit-identical to serial")
    args = ap.parse_args()
    if args.overlap and args.sweep is None:
        ap.error("--overlap only applies to --sweep mode")
    args.moves = tuple(k.strip() for k in args.moves.split(","))
    for kind in args.moves:
        if kind not in M.MOVE_KINDS:
            ap.error(f"--moves: unknown kind {kind!r} (expected a subset "
                     f"of {','.join(M.MOVE_KINDS)})")
    if args.sweep is not None:
        if args.out_dir is None:
            ap.error("--sweep requires --out-dir")
        args.sweep = [float(f) for f in args.sweep.split(",")]
    return args


def build_model_data(args):
    if args.full:
        model = CNN(CNNConfig.resnet18(10, 32))
        data = SyntheticImages(ImageDatasetCfg.cifar10())
    else:
        model = CNN(CNNConfig("r18-mini", 4, args.image_size,
                              ((8, 2, 1), (16, 2, 2)), stem_channels=8))
        data = SyntheticImages(ImageDatasetCfg(
            n_classes=4, image_size=args.image_size, n_train=256, n_test=64))
    return model, data


def make_closures(model, data):
    """The shared training/eval closures (all deterministic in their
    inputs, so a resumed process rebuilds identical ones)."""
    opt = opt_lib.sgd(lr=5e-2, momentum=0.9)
    step, _ = train_lib.make_cnn_train_step(model, opt)
    batches_np = data.batches("train", 32)
    batches = lambda i: {k: jnp.asarray(v)
                         for k, v in batches_np(i).items()}

    def sloss(p, a, batch, soft):
        logits = model.forward(p, a, batch["images"], soft=soft)
        return train_lib.cross_entropy(logits, batch["labels"]), 0.0

    test_b = {k: jnp.asarray(v) for k, v in data.eval_set(64).items()}

    def test_acc(p, m):
        logits = model.forward(p, M.as_device(m), test_b["images"])
        return float(jnp.mean((jnp.argmax(logits, -1) == test_b["labels"])
                              .astype(jnp.float32)) * 100)

    return opt, step, batches, sloss, test_acc


def train_base(model, step, opt, batches, masks0):
    params = model.init(jax.random.PRNGKey(0))
    ostate = opt.init(params)
    mdev = M.as_device(masks0)
    for i in range(80):
        params, ostate, _loss, _acc = step(params, ostate, mdev, batches(i))
    return params


def make_bcd_evaluator(args, model, eval_b, holder, chunk_size, rt):
    """The candidate engine (shared family-agnostic builder —
    ``launch.sweep.make_bcd_evaluator``); returns (evaluator, eval_acc,
    set_ctx).  Share-tied coordinates are overridden outside the fused
    conv/matmul kernels (linearize._apply_share_ties), so the gate stays
    un-fused when the move set can produce ties."""
    return sweep_lib.make_bcd_evaluator(
        args.engine, model, eval_b, holder, chunk_size=chunk_size, rt=rt,
        fused_kernels="share" not in args.moves)


def run_sweep_mode(args):
    model, data = build_model_data(args)
    opt, step, batches, sloss, test_acc = make_closures(model, data)
    masks0 = linearize.init_masks(model.mask_sites())
    total = M.count(masks0)
    b_ref = int(total * args.ref_frac)
    budgets = [int(total * f) for f in args.sweep]
    print(f"total ReLUs {total}; B_ref={b_ref}; schedule={budgets}")

    sweep_cfg = sweep_lib.SweepConfig(
        budgets=budgets, out_dir=args.out_dir, name=model.cfg.name,
        overlap=args.overlap, verbose=True)
    coordinator = coord_lib.from_env(
        default_root=os.path.join(args.out_dir, "coord"))
    if runner.stage_init_exists(sweep_lib.init_dir(sweep_cfg)):
        # resume: params/masks come from the persisted warm start — the
        # untrained init only provides restore templates
        print(f"== reusing persisted warm start under "
              f"{sweep_lib.init_dir(sweep_cfg)} (skipping train + SNL)")
        init = {"kind": "snl", "masks": masks0,
                "params": model.init(jax.random.PRNGKey(0))}
    else:
        print("== train + SNL to B_ref (the sweep's warm start)")
        params = train_base(model, step, opt, batches, masks0)
        alphas = {k: jnp.ones(v.shape) for k, v in masks0.items()}
        res_ref = run_snl(params, alphas, sloss, batches,
                          SNLConfig(b_target=b_ref, lam0=5e-4, kappa=1.5,
                                    epochs=6, steps_per_epoch=5, lr=3e-2,
                                    finetune_steps=15), verbose=True)
        init = res_ref.stage_init()

    holder = {"params": init["params"]}
    eval_b = data.train_eval_set(128)
    evaluator, eval_acc, set_ctx = make_bcd_evaluator(
        args, model, eval_b, holder, args.chunk_size, rt=6)

    def set_params(p):
        holder["params"] = p
        set_ctx(p)

    def ft(m):
        set_params(finetune(holder["params"], m, sloss, batches,
                            steps=12, lr=1e-2))

    def make_bcd_cfg(budget):
        return bcd.BCDConfig(
            b_target=budget, drc=max(1, (b_ref - budgets[-1]) // 10), rt=6,
            adt=0.3, chunk_size=args.chunk_size,
            moves=args.moves, proposal=args.proposal)

    # the reporting tail: pure in (params, masks), so with --overlap it can
    # score stage i on a worker thread while stage i+1's descent mutates the
    # live holder.  The finetuned params are reporting-only — the descent
    # lineage continues from the descent-end state in both modes.
    def stage_ft(p, m):
        return finetune(p, m, sloss, batches, steps=12, lr=1e-2)

    payload = sweep_lib.run_sweep(
        sweep_cfg, make_bcd_cfg, eval_acc, init=init, finetune=ft,
        evaluator=evaluator if args.engine != "sequential" else None,
        params_io=(lambda: holder["params"], set_params),
        stage_finetune=stage_ft,
        stage_eval=lambda m, p: test_acc(p, m),
        notes={"engine": args.engine, "overlap": args.overlap,
               "moves": list(args.moves), "proposal": args.proposal},
        coordinator=coordinator)

    print(f"\n=== sweep curve ({payload['artifact']}) ===")
    for s in payload["stages"]:
        acc = s.get("test_acc")
        print(f"B={s['budget']:6d}  steps={s['steps']:3d}  "
              f"acc={acc if acc is not None else float('nan'):.2f}%  "
              f"masks={s['mask_fingerprint'][:12]}")
        kinds = s.get("move_stats", {}).get("kinds", {})
        if kinds:
            rates = "  ".join(
                f"{k}={v['accepted']}/{v['proposed']}"
                for k, v in sorted(kinds.items()))
            print(f"         accepted/proposed: {rates}")
    return payload


def run_head_to_head(args):
    model, data = build_model_data(args)
    opt, step, batches, sloss, test_acc = make_closures(model, data)
    masks0 = linearize.init_masks(model.mask_sites())
    total = M.count(masks0)
    b_ref = int(total * args.ref_frac)
    b_target = int(total * args.target_frac)
    print(f"total ReLUs {total}; B_ref={b_ref}; B_target={b_target}")

    params = train_base(model, step, opt, batches, masks0)

    alphas = {k: jnp.ones(v.shape) for k, v in masks0.items()}
    print("== SNL to B_ref (the paper's starting checkpoint)")
    res_ref = run_snl(params, alphas, sloss, batches,
                      SNLConfig(b_target=b_ref, lam0=5e-4, kappa=1.5,
                                epochs=6, steps_per_epoch=5, lr=3e-2,
                                finetune_steps=15), verbose=True)
    print("== SNL straight to B_target (baseline)")
    res_snl = run_snl(params, alphas, sloss, batches,
                      SNLConfig(b_target=b_target, lam0=5e-4, kappa=1.5,
                                epochs=6, steps_per_epoch=5, lr=3e-2,
                                finetune_steps=15))
    acc_snl = test_acc(res_snl.params, res_snl.masks)

    print(f"== BCD from B_ref to B_target (ours, engine={args.engine})")
    eval_b = data.train_eval_set(128)
    holder = {"params": res_ref.params}
    bcd_cfg = bcd.BCDConfig(
        b_target=b_target, drc=max(1, (b_ref - b_target) // 5), rt=6,
        adt=0.3, chunk_size=args.chunk_size,
        moves=args.moves, proposal=args.proposal)
    evaluator, eval_acc, set_ctx = make_bcd_evaluator(
        args, model, eval_b, holder, bcd_cfg.chunk_size, bcd_cfg.rt)

    def ft(m):
        holder["params"] = finetune(holder["params"], m, sloss, batches,
                                    steps=12, lr=1e-2)
        set_ctx(holder["params"])

    res_bcd = bcd.run_bcd(res_ref.masks, bcd_cfg, eval_acc, finetune=ft,
                          evaluator=evaluator, verbose=True)
    acc_bcd = test_acc(holder["params"], res_bcd.masks)

    print(f"\n=== results at B_target={b_target} ===")
    print(f"SNL : test acc {acc_snl:.2f}%")
    print(f"BCD : test acc {acc_bcd:.2f}%  (budget exact: "
          f"{M.relu_cost(res_bcd.masks) == b_target})")
    kinds = res_bcd.move_stats.get("kinds", {})
    if len(args.moves) > 1 and kinds:
        print("BCD accepted/proposed by kind: " + "  ".join(
            f"{k}={v['accepted']}/{v['proposed']}"
            for k, v in sorted(kinds.items())))


def main():
    args = parse_args()
    # before any jit: re-runs and resumed sweeps then reuse compiled
    # executables instead of paying re-jit (the cache key covers jax/XLA
    # versions + options, so stale dirs are cold, not wrong)
    compile_cache.enable()
    with tracing.recording() as rec:
        if args.sweep is not None:
            run_sweep_mode(args)
        else:
            run_head_to_head(args)
    print(compile_cache.log_line(rec.counts))


if __name__ == "__main__":
    main()
