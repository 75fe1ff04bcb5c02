"""Candidate-evaluation engine throughput: sequential vs batched vs sharded
vs pipelined vs suffix (prefix-reuse).

Measures candidates/sec for each core.engine backend on the mini ResNet
config — the number that bounds BCD wall-clock (Alg. 2 evaluates up to RT
candidates per outer step).  The timed loop reproduces ``run_bcd``'s real
trial loop: chunk mask trees are *materialized from removal indices inside
the loop* and driven through ``engine.evaluate_prefetched`` (site-aware
backends additionally run the real site-major plan + per-step prefix
computation), so every backend pays exactly what the real loop pays.
Every vmapped backend is constructed at the finetune-ready operating
point the example pipeline uses (``make_param_eval_fn`` +
``context=params``): params are a jit input swapped via ``set_context``,
not a closure constant XLA could fold the mask-independent stem through.

Two workloads:

* the main ``backends`` table samples removal blocks from the GLOBAL active
  set (the Alg. 2 default).  Global blocks almost always touch a shallow
  site, so the suffix backend's cost model falls most chunks back to the
  full forward — its row measures that fallback overhead, not the reuse win;
* ``per_site_depth`` samples *site-local* blocks at a shallow / middle /
  deep site and times suffix vs batched on each — the regime where
  candidates are local edits and the prefix-reuse engine shines;
* ``move_mix`` drives typed candidates over all five move kinds
  (core.masks.sample_moves) through the batched backend and reports the
  throughput ratio against removal-only blocks — the move vocabulary's
  trial-loop overhead, kept outside ``config`` so committed-baseline
  compares don't treat the workload mix as an operating-point change.  The
  headline keys are explicit about what they summarize:
  ``speedup_suffix_vs_batched_deep`` (deep-site ratio),
  ``..._shallow`` (all-fallback floor), ``..._mean`` (mean over the three
  depth classes) and ``..._aggregate`` (global workload, the main table's
  suffix/batched ratio).  CI gates deep+mean relative to the committed
  baseline and floors mean/shallow absolutely
  (benchmarks/check_bench_regression.py --gate-speedup / --floor).

When a ``BENCH_history.jsonl`` is present, the suffix evaluator's cost
model is calibrated from its per-depth measurements
(``SuffixCostModel.calibrated`` — EWMA per site over entries matching
this run's config fingerprint), so the bench exercises the measured
decision path; a first run (no history) uses the analytic prior.

Emits the repo's CSV row format plus a machine-readable
``BENCH_bcd_eval.json``, and appends one line per run to the append-only
``BENCH_history.jsonl`` so the perf trajectory is recorded across PRs.

    PYTHONPATH=src python -m benchmarks.bench_bcd_eval \
        [--rt 32] [--chunk-size 8] [--prefetch auto] [--repeats 3] \
        [--out BENCH_bcd_eval.json] [--history BENCH_history.jsonl]

JAX's persistent compilation cache is on (``launch.compile_cache``:
``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``); the hit count
prints at exit.
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import time

import numpy as np
import jax

from repro.analysis.roofline import SuffixCostModel
from repro.configs import ARCH_IDS, get_config
from repro.core import engine, linearize, masks as M, tracing
from repro.data import ImageDatasetCfg, MarkovTokens, SyntheticImages
from repro.launch import compile_cache, mesh as mesh_lib
from repro.models.lm import LM
from repro.models.resnet import CNN, CNNConfig


def build_pipeline(image_size=16, eval_batch=128):
    """Mini ResNet config (same code path as the paper's ResNet18)."""
    model = CNN(CNNConfig("r18-mini", 4, image_size,
                          ((8, 2, 1), (16, 2, 2)), stem_channels=8))
    data = SyntheticImages(ImageDatasetCfg(
        n_classes=4, image_size=image_size, n_train=256, n_test=64))
    params = model.init(jax.random.PRNGKey(0))
    batch = data.train_eval_set(eval_batch)
    masks0 = linearize.init_masks(model.mask_sites())
    return model, params, batch, masks0


def build_pipeline_family(arch: str, eval_batch=4, seq=33):
    """Per-family row: an LM arch at its reduced config on Markov tokens.

    Same downstream contract as the ResNet pipeline (``make_param_eval_fn``
    / ``make_suffix_eval_fns``); for scanned-stack families the deep depth
    site is a per-repeat virtual site (``s0.rwkv@1``), so its suffix row
    times the carry-checkpointed mid-scan cut."""
    cfg = get_config(arch).reduced()
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mt = MarkovTokens(cfg.vocab, seed=0)
    batch = {"tokens": mt.batch(eval_batch, seq, 10**6)["tokens"]}
    masks0 = linearize.init_masks(model.mask_sites())
    return model, params, batch, masks0


def time_backend(evaluator, masks0, indices, chunk_size, repeats,
                 warmup=True):
    """Drive the real trial loop (materialize per chunk, prefetch-aware;
    site-aware backends run the site-major plan with per-sweep prefix
    recomputation — the per-BCD-step cost); return (cands/sec, us/cand).
    ``indices`` is an (n, k) removal array or a list of typed
    ``masks.Move`` candidates (the move-mix workload).  warmup=False skips
    the untimed compile-and-cache sweep (the evaluator was already
    warmed)."""
    # Match _select_block's chunk policy so the benchmark pays the same
    # per-chunk materialization cost the real loop pays.
    chunk_size = engine.effective_chunk(evaluator, chunk_size)
    flat, layout = M._flatten(masks0)
    typed = isinstance(indices, (list, tuple))
    n = len(indices)
    sited = getattr(evaluator, "site_aware", False)

    def sweep():
        if sited:
            evaluator.begin_step(masks0)
            order, chunks = engine.plan_sited_chunks(
                evaluator, indices, layout, chunk_size)
            gen = engine.materialize_sited(flat, layout, indices, order,
                                           chunks)
        elif typed:
            gen = M.materialize_move_chunks(flat, layout, indices,
                                            chunk_size)
        else:
            gen = M.materialize_chunks(flat, layout, indices, chunk_size)
        for accs in engine.evaluate_prefetched(evaluator, gen):
            pass

    if warmup:
        sweep()                              # warmup: compile + cache
    t0 = time.perf_counter()
    for _ in range(repeats):
        sweep()
    dt = time.perf_counter() - t0
    total = n * repeats
    return total / dt, dt / total * 1e6


def depth_sites(model):
    """Representative shallow / middle / deep cut sites (forward order)."""
    order = model.site_order()
    return {"shallow": order[0], "middle": order[len(order) // 2],
            "deep": order[-1]}


def append_history(path, report):
    """Append one compact line to the append-only perf-trajectory log."""
    try:
        git = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True,
                             timeout=10).stdout.strip() or None
    except Exception:
        git = None
    entry = {
        "utc": datetime.datetime.now(datetime.timezone.utc)
        .strftime("%Y-%m-%dT%H:%M:%SZ"),
        "git": git,
        "config": report["config"],
        "cands_per_s": {k: v["cands_per_s"]
                        for k, v in report["backends"].items()},
        # per-depth rows feed SuffixCostModel.calibrated on later runs
        "per_site_depth": report["per_site_depth"],
        **{k: v for k, v in report.items() if k.startswith("speedup_")},
    }
    with open(path, "a") as f:
        json.dump(entry, f, separators=(",", ":"))
        f.write("\n")


def main():
    ap = argparse.ArgumentParser()
    # Defaults target the regime BCD actually runs in: a small train-subset
    # eval batch (the paper scores candidates on a subsample, not the full
    # set), where per-candidate dispatch/transfer/sync overhead is the
    # bottleneck the batched engine exists to amortize.  chunk-size defaults
    # to 8 (several chunks per RT sweep) so the pipelined backend has chunk
    # boundaries to overlap across; pass --chunk-size == --rt for the
    # one-call-per-sweep operating point.
    ap.add_argument("--rt", type=int, default=32)
    ap.add_argument("--chunk-size", type=int, default=8)
    # "auto" = measured-rate tuning (PrefetchAutoTuner): the depth locks
    # during the untimed warmup sweep, so timed sweeps run at the tuned
    # depth — same flag the example pipeline's sweep jobs pass.
    ap.add_argument("--prefetch",
                    type=lambda v: v if v == "auto" else int(v),
                    default="auto")
    # repeats: timed sweeps per measurement.  8 makes each timing window
    # ~0.3 s on the mini config — long enough that scheduler noise on a
    # 1-2 core host averages out instead of dominating a single sweep.
    ap.add_argument("--repeats", type=int, default=8)
    # Trials interleave across backends and each backend reports its MEDIAN
    # trial: on shared/noisy hosts (CI, this 2-core container) a single
    # measurement can swing ±30%, and a best-of would bias the committed
    # baseline to its upper envelope — making the CI regression gate fire
    # on ordinary noise.  The default is 5 so the committed baseline's
    # cross-backend ratios settle near their true values (the suffix
    # fallback path sits within a few percent of batched, so 3-trial
    # medians of the aggregate ratio still wander either side of parity);
    # CI's PR gate passes --trials 3 to trade precision for runtime.
    ap.add_argument("--trials", type=int, default=5)
    ap.add_argument("--drc", type=int, default=64)
    ap.add_argument("--arch", default="resnet",
                    choices=["resnet"] + list(ARCH_IDS),
                    help="workload family: 'resnet' (the default mini-CNN "
                         "row) or an LM arch id at its reduced config — "
                         "per-family rows land in the same history file, "
                         "keyed by config.model")
    ap.add_argument("--eval-batch", type=int, default=4)
    ap.add_argument("--out", default="BENCH_bcd_eval.json")
    ap.add_argument("--history", default=None,
                    help="append-only perf log (default: BENCH_history.jsonl"
                         " next to --out; pass 'none' to skip)")
    args = ap.parse_args()

    compile_cache.enable()
    with tracing.recording() as rec:
        report = measure(args)
    print(compile_cache.log_line(rec.counts))
    return report


def measure(args):
    if args.arch == "resnet":
        model, params, batch, masks0 = build_pipeline(
            eval_batch=args.eval_batch)
    else:
        model, params, batch, masks0 = build_pipeline_family(
            args.arch, eval_batch=args.eval_batch)
    repeat_sites = getattr(model, "site_repeats", lambda: None)()
    indices = M.sample_removal_indices(
        np.random.default_rng(0), masks0, args.drc, args.rt)
    # Don't let ragged-chunk padding exceed RT: with rt < chunk_size the
    # batched backend would evaluate padding candidates that can never
    # exist (sharded may still round up to the device count).
    chunk = min(args.chunk_size, args.rt)

    history = args.history
    if history is None:
        history = os.path.join(os.path.dirname(args.out) or ".",
                               "BENCH_history.jsonl")
    # Calibrate the suffix cost model from prior runs at this operating
    # point (missing/legacy history -> analytic prior, measured=None).
    fingerprint = {"model": model.cfg.name, "chunk_size": chunk,
                   "eval_batch": args.eval_batch,
                   "n_devices": jax.device_count(),
                   "backend": jax.default_backend()}
    cost_model = SuffixCostModel() if history == "none" else \
        SuffixCostModel.calibrated(history, fingerprint=fingerprint)
    if cost_model.measured:
        print(f"suffix cost model: calibrated from {history} "
              f"({len(cost_model.measured)} site points)")

    eval_acc = model.make_eval_acc(params, batch)
    # All vmapped backends run at the finetune-ready operating point the
    # example pipeline uses (examples/resnet18_bcd_pipeline.py): params ride
    # as evaluator *context* (a jit input swapped via set_context after each
    # finetune), never a baked closure constant.  Closure-of-params lets XLA
    # constant-fold the whole mask-independent stem (and delete init-valued
    # bn affines outright) — a compiled graph no real run ever executes, and
    # one that skews every cross-backend ratio.
    eval_fn_p = model.make_param_eval_fn(batch)
    suffix_ctx = {"params": params,
                  "batch": {k: np.asarray(v) for k, v in batch.items()}}
    # Measurement order per trial: suffix runs back-to-back with batched —
    # their paired ratio is the headline number, and adjacency minimizes the
    # host-drift window inside each pair.
    backends = {
        "sequential": engine.SequentialEvaluator(eval_acc),
        "batched": engine.BatchedEvaluator(eval_fn_p, pad_to=chunk,
                                           context=params),
        "suffix": engine.SuffixEvaluator(
            model.make_suffix_eval_fns(), pad_to=chunk, context=suffix_ctx,
            prefetch=args.prefetch, cost_model=cost_model),
        "sharded": engine.ShardedEvaluator(
            eval_fn_p, mesh_lib.make_candidate_mesh(), pad_to=chunk,
            context=params),
        "pipelined": engine.PipelinedEvaluator(
            eval_fn_p, pad_to=chunk, context=params,
            prefetch=args.prefetch),
    }

    trials = {name: [] for name in backends}
    for trial in range(max(1, args.trials)):
        for name, ev in backends.items():
            cps, _ = time_backend(ev, masks0, indices, chunk, args.repeats,
                                  warmup=(trial == 0))
            trials[name].append(cps)
    results = {}
    for name, cands in trials.items():
        cps = float(np.median(cands))
        results[name] = {"cands_per_s": round(cps, 2),
                         "us_per_cand": round(1e6 / cps, 2)}
        print(f"bcd_eval_{name},{1e6 / cps:.1f},{cps:.1f}")

    def paired_speedup(a, b):
        """median over trials of the within-trial a/b ratio.

        Backends interleave inside each trial (seconds apart), so a paired
        ratio cancels the minutes-scale host-speed drift that a
        ratio-of-medians is exposed to — on shared/throttled hosts the two
        estimators can disagree by several percent on backends that are
        near parity."""
        return round(float(np.median([x / y for x, y
                                      in zip(trials[a], trials[b])])), 2)

    # --- per-site-depth breakdown: site-local removal blocks, the regime
    # where every candidate in a chunk shares a deep prefix
    fractions = model.site_prefix_fractions()
    per_depth = {}
    for depth, site in depth_sites(model).items():
        site_idx = M.sample_removal_indices_within(
            np.random.default_rng(1), masks0, args.drc, args.rt, [site],
            repeat_sites=repeat_sites)
        rows = {"batched": [], "suffix": []}
        for name in rows:                     # compile + tune, untimed
            time_backend(backends[name], masks0, site_idx, chunk, 1)
        # sweep-level pairing: alternate single batched / suffix sweeps so
        # each ratio sample spans ~2 sweeps of wall-clock — host-speed
        # drift (minutes-scale on shared runners) cancels inside the pair,
        # which trial-level pairing can't do for near-parity rows
        for _ in range(max(1, args.trials) * args.repeats):
            for name in rows:
                cps, _ = time_backend(backends[name], masks0, site_idx,
                                      chunk, 1, warmup=False)
                rows[name].append(cps)
        b = float(np.median(rows["batched"]))
        s = float(np.median(rows["suffix"]))
        ratio = round(float(np.median([x / y for x, y
                                       in zip(rows["suffix"],
                                              rows["batched"])])), 2)
        frac = float(fractions[site])
        # what the evaluator's cost model decided for this site-local
        # workload (cold trie): "suffix" rows are real prefix-reuse
        # measurements — the only ones calibration may consume
        mode = "suffix" if cost_model.use_suffix(frac, chunk) else "fallback"
        per_depth[depth] = {
            "site": site,
            "prefix_fraction": round(frac, 4),
            "mode": mode,
            "batched_cands_per_s": round(b, 2),
            "suffix_cands_per_s": round(s, 2),
            "speedup_suffix_vs_batched": ratio,
        }
        print(f"bcd_eval_suffix_{depth},{site},{mode},"
              f"{per_depth[depth]['speedup_suffix_vs_batched']:.2f}x")

    # --- move-mix workload: typed candidates over all five kinds through
    # the batched backend, vs the same backend on removal-only blocks.
    # Prices the move vocabulary's trial-loop overhead (host-side
    # multi-coordinate application: off/on/tie assignment per candidate
    # instead of one put_along_axis) — a pure-overhead row, not a speedup
    # claim, reported outside ``config`` so baseline compares don't treat
    # the workload mix as an operating-point change.
    mixed_moves = M.sample_moves(
        np.random.default_rng(2), masks0, args.drc, args.rt,
        kinds=M.MOVE_KINDS, max_remove=4 * args.drc)
    move_rows = {"removal": [], "moves": []}
    time_backend(backends["batched"], masks0, mixed_moves, chunk, 1)
    for _ in range(max(1, args.trials)):
        cps, _ = time_backend(backends["batched"], masks0, indices, chunk,
                              args.repeats, warmup=False)
        move_rows["removal"].append(cps)
        cps, _ = time_backend(backends["batched"], masks0, mixed_moves,
                              chunk, args.repeats, warmup=False)
        move_rows["moves"].append(cps)
    move_mix = {
        "kinds": list(M.MOVE_KINDS),
        "removal_cands_per_s": round(float(np.median(
            move_rows["removal"])), 2),
        "moves_cands_per_s": round(float(np.median(
            move_rows["moves"])), 2),
        "ratio_moves_vs_removal": round(float(np.median(
            [x / y for x, y in zip(move_rows["moves"],
                                   move_rows["removal"])])), 2),
    }
    print(f"bcd_eval_move_mix,batched,"
          f"{move_mix['ratio_moves_vs_removal']:.2f}x")

    report = {
        "bench": "bcd_eval",
        "config": {"rt": args.rt, "chunk_size": chunk,
                   "prefetch": args.prefetch,
                   "drc": args.drc, "repeats": args.repeats,
                   "trials": args.trials,
                   "eval_batch": args.eval_batch,
                   "model": model.cfg.name,
                   "n_devices": jax.device_count(),
                   "backend": jax.default_backend(),
                   "calibrated": bool(cost_model.measured),
                   # provenance: identifies what produced a committed
                   # baseline without entering the operating-point compare
                   "provenance": {
                       "jax": jax.__version__,
                       "platform": jax.default_backend(),
                       "device_kind": jax.devices()[0].device_kind,
                   }},
        "backends": results,
        "per_site_depth": per_depth,
        "move_mix": move_mix,
        "speedup_batched_vs_sequential":
            paired_speedup("batched", "sequential"),
        "speedup_sharded_vs_sequential":
            paired_speedup("sharded", "sequential"),
        "speedup_pipelined_vs_sequential":
            paired_speedup("pipelined", "sequential"),
        "speedup_pipelined_vs_batched":
            paired_speedup("pipelined", "batched"),
        # headline prefix-reuse numbers, each with an explicit suffix: the
        # deep-site ratio, the shallow all-fallback floor, the mean over
        # depth classes (deep+mean CI-gated vs baseline; mean+shallow
        # floored absolutely), and the global-workload aggregate
        "speedup_suffix_vs_batched_deep":
            per_depth["deep"]["speedup_suffix_vs_batched"],
        "speedup_suffix_vs_batched_shallow":
            per_depth["shallow"]["speedup_suffix_vs_batched"],
        "speedup_suffix_vs_batched_mean": round(
            float(np.mean([d["speedup_suffix_vs_batched"]
                           for d in per_depth.values()])), 2),
        "speedup_suffix_vs_batched_aggregate":
            paired_speedup("suffix", "batched"),
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    if history != "none":
        append_history(history, report)
    print(f"batched vs sequential: "
          f"{report['speedup_batched_vs_sequential']:.2f}x; "
          f"suffix vs batched: deep "
          f"{report['speedup_suffix_vs_batched_deep']:.2f}x, shallow "
          f"{report['speedup_suffix_vs_batched_shallow']:.2f}x, mean "
          f"{report['speedup_suffix_vs_batched_mean']:.2f}x, aggregate "
          f"{report['speedup_suffix_vs_batched_aggregate']:.2f}x"
          f"  -> {args.out}")
    return report


if __name__ == "__main__":
    main()
