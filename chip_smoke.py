"""Smoke run on the TPU: the quickest proof that the system starts on the chip.

    python3 chip_smoke.py              # one chip: kernels, bcd, serve
    python3 chip_smoke.py --chips 4    # the full-forward evaluator over a
                                       # 4-chip mesh, against it on 1 chip

One process holds the chip throughout and starts no children.  It fails at
once unless JAX's first device is a TPU.  Phases run in order, each through
the entry points a user calls, and each checks its results:

* kernels — every Pallas kernel on a model path at the widths the models
  run, against its jnp oracle, with ``tpu_custom_call`` in its compiled
  text; the hard-mask gate the models differentiate (XLA's, no kernel)
  against the oracle's gradient.
* bcd — ResNet18 on synthetic CIFAR-10 at real shapes, built as
  ``examples/resnet18_bcd_pipeline.py --full`` builds it: train steps, a
  short SNL to B_ref, then BCD outer steps through the ``suffix`` engine
  with fused kernels on and finetuning between steps.  The suffix engine's
  accuracies on the first step's candidates must agree with the
  ``sequential`` engine's, and the final billable ReLU count must equal the
  budget reached.
* serve — ``ServeLoop`` on stablelm-1.6b at its published width (random
  weights from the seed) with two mask sets; every served token must be
  within tolerance of the maximum of its row of a plain ``model.forward``
  under the same mask set, and every bill must equal ``pi_cost``'s.

Each phase prints its wall, XLA-compile and remaining ("steady") seconds as
smoke timings — not benchmark results.  Any failure exits non-zero before
the last line, which on success is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "examples")]

SEED = 0
# Accuracies are percentages over a 128-image eval batch: two evaluators that
# sum in different orders (fused conv vs lax.conv, sharded vs one-device
# batch statistics) may flip one near-tied image, and no more.
EVAL_BATCH = 128
ACC_TOL = 100.0 / EVAL_BATCH + 1e-6
# A served token may lose its row's argmax in a plain forward by at most
# this share of the row's logit range: decode with a KV cache and a full
# bf16 forward round differently.
SERVE_LOGIT_TOL = 0.02


class Phase:
    """Times one phase: wall seconds, and XLA-compile seconds from JAX's
    monitoring events (the rest of the wall is reported as steady)."""

    def __init__(self, name: str):
        self.name = name
        self.compile_s = 0.0

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        print(f"== phase {self.name}", flush=True)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        import jax
        wall = time.perf_counter() - self.t0
        jax.monitoring.unregister_event_duration_listener(self._on)
        if exc_type is None:
            print(f"[smoke timing] phase={self.name} wall_s={wall:.3f} "
                  f"compile_s={self.compile_s:.3f} "
                  f"steady_s={wall - self.compile_s:.3f}", flush=True)
        return False


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ------------------------------------------------------------------ kernels


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite kernel output")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                    1e-30))


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import linearize
    from repro.kernels import masked_act as K, ref

    bf16, f32 = jnp.bfloat16, jnp.float32
    d_ff, d_model = 5632, 2048            # stablelm-1.6b FFN
    site = 32 * 32 * 64                   # ResNet18 stem site, 32x32 input

    def normal(shape, dtype, scale=1.0):
        return lambda k: (jax.random.normal(k, shape, f32) * scale
                          ).astype(dtype)

    def mask(shape):
        return lambda k: jax.random.bernoulli(k, 0.5, shape).astype(f32)

    conv_ref_b = jax.vmap(ref.masked_act_conv3x3_ref, in_axes=(0, 0, None))
    mm_ref_b = jax.vmap(
        lambda x, m, w, u: ref.masked_act_matmul_ref(x, m, w, u, kind="silu"),
        in_axes=(0, 0, None, 0))
    # (kernel, oracle, argument makers, tolerance on max|err| / max|oracle|)
    # Elementwise kernels match to an ulp of their dtype; fused matmul/conv
    # sum in another order than XLA's, and bf16 rounds its output.
    cases = {
        "masked_act_2d/resnet18_stem": (
            K.masked_act_2d, ref.masked_act_ref,
            [normal((EVAL_BATCH, site), f32), mask((site,))], 1e-6),
        "masked_act_2d/stablelm_prefill": (
            lambda x, m: K.masked_act_2d(x, m, kind="silu"),
            lambda x, m: ref.masked_act_ref(x, m, kind="silu"),
            [normal((512, d_ff), bf16), mask((d_ff,))], 1e-2),
        "masked_act_2d/stablelm_decode": (
            lambda x, m: K.masked_act_2d(x, m, kind="silu"),
            lambda x, m: ref.masked_act_ref(x, m, kind="silu"),
            [normal((4, d_ff), bf16), mask((d_ff,))], 1e-2),
        "masked_act_2d_batched/resnet18_stem": (
            K.masked_act_2d_batched,
            lambda x, m: ref.masked_act_ref(x, m[:, None, :]),
            [normal((8, EVAL_BATCH, site), f32), mask((8, site))], 1e-6),
        **{f"masked_act_conv3x3/resnet18_{hw}x{hw}x{c}": (
            K.masked_act_conv3x3, ref.masked_act_conv3x3_ref,
            [normal((EVAL_BATCH, hw, hw, c), f32), mask((hw, hw, c)),
             normal((3, 3, c, c), f32, (9 * c) ** -0.5)], 2e-2)
           for hw, c in ((32, 64), (16, 128), (8, 256), (4, 512))},
        "masked_act_conv3x3_batched/resnet18_4x4x512": (
            K.masked_act_conv3x3_batched, conv_ref_b,
            [normal((8, EVAL_BATCH, 4, 4, 512), f32), mask((8, 4, 4, 512)),
             normal((3, 3, 512, 512), f32, (9 * 512) ** -0.5)], 2e-2),
        "masked_act_matmul_2d/stablelm_down_proj": (
            lambda x, m, w, u: K.masked_act_matmul_2d(x, m, w, u,
                                                      kind="silu"),
            lambda x, m, w, u: ref.masked_act_matmul_ref(x, m, w, u,
                                                         kind="silu"),
            [normal((512, d_ff), bf16), mask((d_ff,)),
             normal((d_ff, d_model), bf16, d_ff ** -0.5),
             normal((512, d_ff), bf16)], 2e-2),
        "masked_act_matmul_2d_batched/stablelm_down_proj": (
            lambda x, m, w, u: K.masked_act_matmul_2d_batched(x, m, w, u,
                                                              kind="silu"),
            mm_ref_b,
            [normal((8, 64, d_ff), bf16), mask((8, d_ff)),
             normal((d_ff, d_model), bf16, d_ff ** -0.5),
             normal((8, 64, d_ff), bf16)], 2e-2),
    }
    key = jax.random.PRNGKey(SEED)
    for i, (name, (fn, oracle, makers, tol)) in enumerate(cases.items()):
        keys = jax.random.split(jax.random.fold_in(key, i), len(makers))
        args = [jax.jit(mk)(k) for mk, k in zip(makers, keys)]
        compiled = jax.jit(fn).lower(*args).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no tpu_custom_call in the compiled program")
        got = jax.block_until_ready(compiled(*args))
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        warm = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        err = _rel_err(got, want)
        print(f"  {name}: max|err|/max|ref|={err:.3e} (tol {tol:.0e}) "
              f"[smoke timing] warm_call_s={warm:.6f}", flush=True)
        check(err <= tol, f"{name}: error {err:.3e} above {tol:.0e}")

    # the unrouted hard-mask gate at the ResNet18 stem site, as training,
    # finetuning and the plain forwards run it: XLA's, with no kernel, and
    # its gradient is the oracle's
    x = jax.random.normal(jax.random.fold_in(key, 100),
                          (EVAL_BATCH, 32, 32, 64), f32)
    m = jax.random.bernoulli(jax.random.fold_in(key, 101), 0.5,
                             (32, 32, 64)).astype(f32)
    cot = jax.random.normal(jax.random.fold_in(key, 102), x.shape, f32)
    site = linearize.MaskSite((32, 32, 64), "relu")
    step = jax.jit(jax.value_and_grad(
        lambda x: jnp.sum(linearize.apply_masked_act(x, m, site) * cot)))
    check("tpu_custom_call" not in step.lower(x).compile().as_text(),
          "hard-mask gate: a kernel in the differentiated forward")
    _, g = step(x)
    g_ref = jax.jit(jax.grad(
        lambda x: jnp.sum(ref.masked_act_ref(x, m) * cot)))(x)
    err = _rel_err(g, g_ref)
    print(f"  hard-mask gate grad: max|err|/max|ref|={err:.3e} "
          "(tol 1e-6)", flush=True)
    check(err <= 1e-6, f"hard-mask gate: gradient error {err:.3e}")


# ---------------------------------------------------------------------- bcd


def phase_bcd() -> None:
    import jax
    import jax.numpy as jnp
    import resnet18_bcd_pipeline as pipeline
    from repro.core import bcd, engine, linearize, masks as M
    from repro.core.snl import SNLConfig, finetune, run_snl
    from repro.kernels import ops
    from repro.launch import sweep as sweep_lib

    check(ops.fused_dispatch_enabled(), "fused kernels not armed on this chip")
    model, data = pipeline.build_model_data(argparse.Namespace(full=True))
    opt, step, batches, sloss, _test_acc = pipeline.make_closures(model, data)
    masks0 = linearize.init_masks(model.mask_sites())
    total = M.count(masks0)

    # train steps: the compiled step must run the Pallas masked activation
    # in its differentiated forward
    params = model.init(jax.random.PRNGKey(SEED))
    ostate = opt.init(params)
    mdev = M.as_device(masks0)
    compiled = step.lower(params, ostate, mdev, batches(0)).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "train step: masked-act kernel missing from the compiled step")
    losses = []
    for i in range(5):
        params, ostate, loss, _acc = compiled(params, ostate, mdev,
                                              batches(i))
        losses.append(float(loss))
    print(f"  train: 5 steps, loss {losses[0]:.4f} -> {losses[-1]:.4f}",
          flush=True)
    check(bool(np.isfinite(losses).all()), f"non-finite loss {losses}")

    b_ref = int(total * 0.6)
    alphas = {k: jnp.ones(v.shape) for k, v in masks0.items()}
    res_ref = run_snl(params, alphas, sloss, batches,
                      SNLConfig(b_target=b_ref, lam0=5e-4, kappa=1.5,
                                epochs=2, steps_per_epoch=3, lr=3e-2,
                                finetune_steps=3))
    check(M.relu_cost(res_ref.masks) == b_ref, "SNL missed B_ref")
    print(f"  snl: {total} -> B_ref={b_ref} ReLUs", flush=True)

    # drc=1 spreads the candidates' earliest touched sites over the depth of
    # the net, so the suffix engine cuts deep as well as falling back
    cfg = bcd.BCDConfig(b_target=b_ref - 3, drc=1, rt=16, adt=0.3,
                        chunk_size=8)
    holder = {"params": res_ref.params}
    evaluator, eval_acc, set_ctx = sweep_lib.make_bcd_evaluator(
        "suffix", model, data.train_eval_set(EVAL_BATCH), holder,
        chunk_size=cfg.chunk_size, rt=cfg.rt, fused_kernels=True)
    state = bcd.init_state(res_ref.masks, cfg)

    # the first step's candidates (same rng draw as the step will make),
    # through the suffix engine and the sequential reference
    budget = M.relu_cost(state.masks)
    moves = M.sample_moves(copy.deepcopy(state.rng), state.masks,
                           min(cfg.drc, budget - cfg.b_target), cfg.rt,
                           max_remove=budget - cfg.b_target)
    flat, layout = M._flatten(state.masks)
    evaluator.begin_step(state.masks)
    order, chunks = engine.plan_sited_chunks(evaluator, moves, layout,
                                             cfg.chunk_size)
    suffix_accs = np.empty(cfg.rt)
    for chunk, (_, s, e) in zip(
            engine.materialize_sited(flat, layout, moves, order, chunks),
            chunks):
        suffix_accs[order[s:e]] = evaluator.evaluate(chunk)
    seq = engine.SequentialEvaluator(eval_acc)
    seq_accs = np.concatenate([seq.evaluate(c) for c in
                               M.materialize_move_chunks(flat, layout, moves,
                                                         cfg.chunk_size)])
    gap = float(np.max(np.abs(suffix_accs - seq_accs)))
    sited = sum(site is not None for site, _, _ in chunks)
    print(f"  suffix vs sequential on step 0's {cfg.rt} candidates: "
          f"max|dacc|={gap:.4f}pp (tol {ACC_TOL:.4f}pp); chunks "
          f"{sited} sited, {len(chunks) - sited} full-forward", flush=True)
    check(gap <= ACC_TOL, f"suffix engine disagrees by {gap}pp")
    check(sited > 0, "no chunk took the suffix path")

    def ft(m):
        holder["params"] = finetune(holder["params"], m, sloss, batches,
                                    steps=3, lr=1e-2)
        set_ctx(holder["params"])

    for log in bcd.bcd_steps(state, cfg, eval_acc, ft, evaluator=evaluator,
                             verbose=True):
        check(np.isfinite(log.acc_after_finetune), "non-finite accuracy")
        print(f"  [smoke timing] bcd step {log.step} wall_s={log.wall_s:.3f}",
              flush=True)
    bcd.check_reached_target(state, cfg)
    check(len(state.history) == 3, f"{len(state.history)} BCD steps, not 3")
    check(M.relu_cost(state.masks) == cfg.b_target,
          "relu_cost of the result is not the budget reached")
    print(f"  bcd: B={b_ref} -> {M.relu_cost(state.masks)} exact", flush=True)


# -------------------------------------------------------------------- serve


def phase_serve() -> None:
    import jax
    from repro.configs import get_config
    from repro.core import pi_cost
    from repro.launch import serve_loop as sl
    from repro.models.lm import LM

    model = LM(get_config("stablelm_1p6b"))
    params = jax.block_until_ready(jax.jit(model.init)(
        jax.random.PRNGKey(SEED)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    store = sl.threshold_mask_sets(model, (1.0, 0.5), seed=SEED)
    loop = sl.ServeLoop(model, params, store,
                        sl.default_classes(store, max_new_tokens=8),
                        slots=4, max_len=128)
    rng = np.random.default_rng(SEED)
    reqs = [loop.submit(rng.integers(0, model.cfg.vocab,
                                     int(rng.integers(4, 33))),
                        store.names[i % len(store.names)])
            for i in range(8)]
    loop.shutdown(drain=True)
    check(len(loop.completed) == len(reqs) and not loop.shed,
          f"{len(loop.completed)} served, {len(loop.shed)} shed of "
          f"{len(reqs)}")
    print(f"  serve: {n_params / 1e9:.2f}B params, {len(reqs)} requests "
          f"over sets {store.names}", flush=True)

    # plain forward over prompt + served tokens, one padded batch per set
    # (causal: padding after a request's tokens cannot reach them)
    seqs = [np.concatenate([r.prompt, r.tokens[:-1]]) for r in reqs]
    toks = np.zeros((len(reqs), 64), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    fwd = jax.jit(lambda p, m, t: model.forward(p, m, t)[0])
    worst = 0.0
    for name in store.names:
        logits = np.asarray(fwd(params, store.select(name), toks),
                            np.float32)
        for i, r in enumerate(reqs):
            if r.mask_set != name:
                continue
            L = len(r.prompt)
            rows = logits[i, L - 1:L - 1 + len(r.tokens)]
            check(bool(np.isfinite(rows).all()), "non-finite logits")
            gaps = (rows.max(-1) - rows[np.arange(len(r.tokens)), r.tokens]
                    ) / (rows.max(-1) - rows.min(-1))
            worst = max(worst, float(gaps.max()))
            check(bool((gaps <= SERVE_LOGIT_TOL).all()),
                  f"request {r.rid} ({name}): served token off its row's "
                  f"max by {gaps.max():.4f} of the row's range")
            info = store.info(name)
            want = pi_cost.bill_request(
                info.relu_cost, len(store.site_shapes),
                tokens=len(r.prompt) + len(r.tokens), proto=loop.proto,
                mask_set=info.name, fingerprint=info.fingerprint,
                degraded_from=r.degraded_from)
            check(r.bill == want, f"request {r.rid}: bill {r.bill} != {want}")
    print(f"  served tokens vs plain forward: worst gap {worst:.5f} of the "
          f"row's logit range (tol {SERVE_LOGIT_TOL}); bills match",
          flush=True)


# ------------------------------------------------------------- four chips


def phase_mesh() -> None:
    import jax
    from repro.core import engine, linearize, masks as M
    from repro.data import ImageDatasetCfg, SyntheticImages
    from repro.launch import mesh as mesh_lib
    from repro.models.resnet import CNN, CNNConfig

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, not 4")
    model = CNN(CNNConfig.resnet18(10, 32))
    data = SyntheticImages(ImageDatasetCfg.cifar10())
    params = model.init(jax.random.PRNGKey(SEED))
    eval_b = data.train_eval_set(EVAL_BATCH)
    rng = np.random.default_rng(SEED)
    masks = M.sample_removal_block(
        rng, linearize.init_masks(model.mask_sites()), 200_000)
    rt, chunk, adt = 16, 8, 0.3
    moves = M.sample_moves(rng, masks, 20_000, rt)
    flat, layout = M._flatten(masks)
    chunks = list(M.materialize_move_chunks(flat, layout, moves, chunk))

    mesh = mesh_lib.make_cand_batch_mesh(cand=2, batch=2)
    check(mesh.devices.size == 4
          and len({d.id for d in mesh.devices.flat}) == 4,
          f"mesh {mesh} does not span 4 devices")
    ctx = {"params": params,
           "batch": {k: np.asarray(v) for k, v in eval_b.items()}}
    meshed = engine.PipelinedEvaluator(
        model.make_joint_eval_fn(), mesh=mesh, pad_to=chunk, context=ctx,
        context_specs=engine.context_batch_specs(ctx))
    placed = meshed._device_batch(chunks[0])
    for k, leaf in placed.items():
        devs = {s.device.id for s in leaf.addressable_shards}
        slices = {str(s.index) for s in leaf.addressable_shards}
        check(len(devs) == 4 and len(slices) == 4,
              f"candidate chunk leaf {k} not split over 4 devices: "
              f"{len(devs)} devices, {len(slices)} distinct slices")
    one_chip = engine.PipelinedEvaluator(
        model.make_param_eval_fn(eval_b), pad_to=chunk, context=params)
    acc_s = np.concatenate([meshed.evaluate(c) for c in chunks])
    acc_b = np.concatenate([one_chip.evaluate(c) for c in chunks])
    gap = float(np.max(np.abs(acc_s - acc_b)))
    base = float(model.make_eval_acc(params, eval_b)(masks))

    def select(accs):
        drops = base - accs
        hits = np.flatnonzero(drops < adt)
        return int(hits[0]) if hits.size else int(np.argmin(drops))

    same = select(acc_s) == select(acc_b)
    print(f"  meshed (2x2 cand x batch mesh) vs one chip on {rt} "
          f"ResNet18 candidates: max|dacc|={gap:.4f}pp (tol {ACC_TOL:.4f}pp)"
          f"; selected block same: {same} ({select(acc_s)} vs "
          f"{select(acc_b)})", flush=True)
    check(gap <= ACC_TOL, f"meshed evaluator disagrees by {gap}pp")


# --------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the meshed evaluator over 4 chips")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device: {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    from repro.core import tracing
    from repro.launch import compile_cache
    print(f"compile cache: {compile_cache.enable()}", flush=True)

    t0 = time.perf_counter()
    phases = ([("mesh", phase_mesh)] if args.chips == 4 else
              [("kernels", phase_kernels), ("bcd", phase_bcd),
               ("serve", phase_serve)])
    with tracing.recording() as rec:
        for name, fn in phases:
            with Phase(name):
                fn()
    print(f"[smoke timing] total_s={time.perf_counter() - t0:.3f}",
          flush=True)
    print(compile_cache.log_line(rec.counts), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
