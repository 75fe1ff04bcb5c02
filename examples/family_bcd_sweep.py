"""Multi-budget BCD sweep on the LM model families (Fig. 4 protocol on
recurrent/SSM and MoE stacks): train -> SNL(B_ref) warm start -> budget
schedule with finetuning between stages.

    PYTHONPATH=src python examples/family_bcd_sweep.py \
        --arch rwkv6_3b --sweep 0.6,0.45 --out-dir runs/rwkv6
    PYTHONPATH=src python examples/family_bcd_sweep.py \
        --arch deepseek_moe_16b --sweep 0.6,0.45 --out-dir runs/moe \
        [--engine suffix] [--chunk-size 4] [--moves remove,swap,stage_drop]

Same driver stack as examples/resnet18_bcd_pipeline.py (launch.sweep on
core.runner: restartable, overlappable, multi-host-ready) but on
``models.lm`` at each family's ``reduced()`` config with Markov-token
data.  What's family-specific is all below the shared engine contract:

* recurrent families (rwkv6_3b, zamba2_2p7b's mamba blocks) run their
  repeated block group as one ``lax.scan`` over stacked params — the
  suffix engine cuts INSIDE that scan at per-repeat virtual sites
  (``s0.rwkv@1``): the prefix returns the scan carry (the residual
  stream) at repeat r, the suffix resumes the remaining repeats from
  that carry checkpoint (docs/bcd_engine.md §Scanned-stack cuts);
* MoE families (deepseek_moe_16b) route per-expert masked FFNs with
  deterministic capacity overflow, so stacked candidate evaluation is
  bitwise-identical to sequential and every engine stays exact.

After the sweep, the mid-scan suffix path is exercised explicitly: a
block of candidates local to the DEEPEST per-repeat stack site is driven
through the suffix evaluator, asserting that carry-checkpointed sited
chunks actually ran and that their accuracies match the sequential
reference.
"""
import argparse
import os

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.core import bcd, engine, linearize, masks as M, runner, tracing
from repro.core.snl import SNLConfig, finetune, run_snl
from repro.data import MarkovTokens
from repro.launch import compile_cache
from repro.launch import coordinator as coord_lib
from repro.launch import sweep as sweep_lib
from repro.models.lm import LM
from repro.training import train as train_lib


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6_3b", choices=ARCH_IDS,
                    help="model family (reduced config): recurrent "
                         "(rwkv6_3b, zamba2_2p7b), MoE (deepseek_moe_16b, "
                         "mixtral_8x22b), or dense")
    ap.add_argument("--engine", default="suffix",
                    choices=["sequential", "suffix"])
    ap.add_argument("--chunk-size", type=int, default=4)
    ap.add_argument("--moves", default="remove",
                    help="comma-separated move kinds (subset of "
                         f"{','.join(M.MOVE_KINDS)})")
    ap.add_argument("--proposal", default="uniform",
                    choices=list(M.PROPOSALS))
    ap.add_argument("--sweep", default="0.6,0.45",
                    help="descending budget fractions of the total "
                         "nonlinearity count")
    ap.add_argument("--ref-frac", type=float, default=0.75,
                    help="SNL warm-start budget fraction (B_ref)")
    ap.add_argument("--out-dir", required=True)
    ap.add_argument("--overlap", action="store_true")
    ap.add_argument("--train-steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--eval-batch", type=int, default=4)
    args = ap.parse_args()
    args.moves = tuple(k.strip() for k in args.moves.split(","))
    for kind in args.moves:
        if kind not in M.MOVE_KINDS:
            ap.error(f"--moves: unknown kind {kind!r}")
    args.sweep = [float(f) for f in args.sweep.split(",")]
    return args


def make_closures(model, mt, args):
    """Shared training/eval closures — deterministic in their inputs, so a
    resumed process rebuilds identical ones.  Batches follow the LM data
    contract: ``tokens`` (B, S) next-token-shifted against ``labels``."""
    batches_np = lambda i: mt.batch(args.batch, args.seq, i)
    batches = lambda i: {k: jnp.asarray(v)
                         for k, v in batches_np(i).items()}

    def sloss(p, a, batch, soft):
        logits, _ = model.forward(p, a, batch["tokens"], soft=soft)
        return train_lib.cross_entropy(logits, batch["labels"]), 0.0

    # held-out scoring batch (a far-future step the train stream never hits)
    test_b = {k: jnp.asarray(v)
              for k, v in mt.batch(args.eval_batch, args.seq, 10**6).items()}
    test_fn = jax.jit(model.make_param_eval_fn(test_b))

    def test_acc(m, p):
        return float(test_fn(M.as_device(m), p))

    return batches, sloss, test_acc


def check_midscan_suffix(args, model, masks, params, eval_b):
    """Exercise the carry-checkpointed suffix path at a mid-scan stack site.

    Candidates are site-local to the DEEPEST per-repeat virtual site
    (``s<pos>.<kind>@r``, r >= 1): the suffix engine's prefix runs the
    scan up to repeat r and checkpoints the carry; each candidate then
    re-runs only repeats r.. and the tail.  Asserts that such a sited chunk
    ran and that every accuracy matches the sequential reference; returns
    the site, or None when the family has no scanned stack."""
    mid = [s for s in model.site_order()
           if "@" in s and int(s.rsplit("@", 1)[1]) >= 1]
    if not mid:
        print(f"[midscan] {model.cfg.name}: no per-repeat stack sites — "
              "skipping the mid-scan check")
        return None
    site = mid[-1]
    rt = 16
    chunk = min(args.chunk_size, rt)
    indices = M.sample_removal_indices_within(
        np.random.default_rng(7), masks, 8, rt, [site],
        repeat_sites=model.site_repeats())
    ev, eval_acc, _ = sweep_lib.make_bcd_evaluator(
        "suffix", model, eval_b, {"params": params}, chunk_size=chunk,
        rt=rt, fused_kernels="share" not in args.moves)
    flat, layout = M._flatten(masks)
    ev.begin_step(masks)
    order, chunks = engine.plan_sited_chunks(ev, indices, layout, chunk)
    accs = np.concatenate(list(engine.evaluate_prefetched(
        ev, engine.materialize_sited(flat, layout, indices, order,
                                     chunks))))
    sited = [c[0] for c in chunks if c[0] is not None]
    trie = ev.trie
    assert any("@" in s and int(s.rsplit("@", 1)[1]) >= 1 for s in sited) \
        and (trie.misses + trie.extensions) > 0, (
        f"mid-scan candidates at {site} fell back to the full forward "
        f"(sited={sited}) — the carry-checkpoint suffix path did not run")
    want = engine.SequentialEvaluator(eval_acc).evaluate(
        M.materialize_candidates(masks, indices))
    np.testing.assert_allclose(accs, want[order], atol=1e-4)
    frac = float(model.site_prefix_fractions()[site])
    print(f"[midscan] {model.cfg.name} {site}: {rt} candidates on the "
          f"carry-checkpointed suffix path match the sequential reference "
          f"(prefix_fraction={frac:.2f}, trie misses={trie.misses} "
          f"extensions={trie.extensions})")
    return site


def main():
    args = parse_args()
    compile_cache.enable()
    with tracing.recording() as rec:
        payload = run(args)
    print(compile_cache.log_line(rec.counts))
    return payload


def run(args):
    cfg = get_config(args.arch).reduced()
    model = LM(cfg)
    mt = MarkovTokens(cfg.vocab, seed=0)
    batches, sloss, test_acc = make_closures(model, mt, args)
    masks0 = linearize.init_masks(model.mask_sites())
    total = M.count(masks0)
    b_ref = int(total * args.ref_frac)
    budgets = [int(total * f) for f in args.sweep]
    print(f"family={cfg.name} sites={list(model.mask_sites())} "
          f"repeats={model.site_repeats()}")
    print(f"total nonlinearities {total}; B_ref={b_ref}; "
          f"schedule={budgets}")

    sweep_cfg = sweep_lib.SweepConfig(
        budgets=budgets, out_dir=args.out_dir, name=cfg.name,
        overlap=args.overlap, verbose=True)
    coordinator = coord_lib.from_env(
        default_root=os.path.join(args.out_dir, "coord"))
    if runner.stage_init_exists(sweep_lib.init_dir(sweep_cfg)):
        print(f"== reusing persisted warm start under "
              f"{sweep_lib.init_dir(sweep_cfg)} (skipping train + SNL)")
        init = {"kind": "snl", "masks": masks0,
                "params": model.init(jax.random.PRNGKey(0))}
    else:
        print("== train + SNL to B_ref (the sweep's warm start)")
        params = finetune(model.init(jax.random.PRNGKey(0)), masks0, sloss,
                          batches, steps=args.train_steps, lr=3e-3,
                          use_adam=True)
        alphas = {k: jnp.ones(v.shape) for k, v in masks0.items()}
        res_ref = run_snl(params, alphas, sloss, batches,
                          SNLConfig(b_target=b_ref, lam0=5e-4, kappa=1.5,
                                    epochs=4, steps_per_epoch=5, lr=1e-2,
                                    finetune_steps=10), verbose=True)
        init = res_ref.stage_init()

    holder = {"params": init["params"]}
    eval_b = {"tokens": jnp.asarray(
        mt.batch(args.eval_batch, args.seq, 10**6 + 1)["tokens"])}
    evaluator, eval_acc, set_ctx = sweep_lib.make_bcd_evaluator(
        args.engine, model, eval_b, holder, chunk_size=args.chunk_size,
        rt=6, fused_kernels="share" not in args.moves)

    def set_params(p):
        holder["params"] = p
        set_ctx(p)

    def ft(m):
        set_params(finetune(holder["params"], m, sloss, batches,
                            steps=8, lr=1e-3, use_adam=True))

    def make_bcd_cfg(budget):
        return bcd.BCDConfig(
            b_target=budget, drc=max(1, (b_ref - budgets[-1]) // 10), rt=6,
            adt=0.3, chunk_size=args.chunk_size,
            moves=args.moves, proposal=args.proposal)

    def stage_ft(p, m):
        return finetune(p, m, sloss, batches, steps=8, lr=1e-3,
                        use_adam=True)

    payload = sweep_lib.run_sweep(
        sweep_cfg, make_bcd_cfg, eval_acc, init=init, finetune=ft,
        evaluator=evaluator if args.engine != "sequential" else None,
        params_io=(lambda: holder["params"], set_params),
        stage_finetune=stage_ft,
        stage_eval=test_acc,
        notes={"arch": args.arch, "engine": args.engine,
               "overlap": args.overlap,
               "moves": list(args.moves), "proposal": args.proposal},
        coordinator=coordinator)

    print(f"\n=== sweep curve ({payload['artifact']}) ===")
    for s in payload["stages"]:
        acc = s.get("test_acc")
        print(f"B={s['budget']:6d}  steps={s['steps']:3d}  "
              f"acc={acc if acc is not None else float('nan'):.2f}%  "
              f"masks={s['mask_fingerprint'][:12]}")

    if coordinator.is_writer:
        check_midscan_suffix(args, model, payload["final_masks"],
                             holder["params"], eval_b)
    return payload


if __name__ == "__main__":
    main()
