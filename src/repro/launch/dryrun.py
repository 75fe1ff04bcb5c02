"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

    PYTHONPATH=src python -m repro.launch.dryrun                # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_32b \
        --shape train_4k --multi-pod --out reports/dryrun

Per cell it jits the train/prefill/decode step with production shardings,
``.lower().compile()``s it, prints memory_analysis() / cost_analysis(), and
writes a JSON record (roofline terms included) for EXPERIMENTS.md.

NOTE: the XLA_FLAGS line below MUST run before any other import — jax locks
the device count at first init.  Smoke tests / benches never import this
module, so they see the real single CPU device.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import (ARCH_IDS, SHAPES, cell_applicable, get_config,
                           input_specs)
from repro.models.lm import LM
from repro.training import optimizer as opt_lib
from repro.training import serve as serve_lib
from repro.training import train as train_lib
from repro.analysis import roofline as rl
from repro.launch.mesh import dp_axes as mesh_dp_axes, make_production_mesh


def _mask_sds(model):
    sites = model.mask_sites()
    return {k: jax.ShapeDtypeStruct(s.shape, jnp.float32)
            for k, s in sites.items()}


def lower_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
               fsdp: bool = True, remat: bool = True, donate: bool = True,
               overrides: dict | None = None, loss_chunk: int = 0):
    """Returns (lowered, meta) for one cell."""
    import dataclasses
    cfg = get_config(arch_id)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = mesh_dp_axes(mesh)
    model = LM(cfg)
    specs = input_specs(cfg, shape)
    mask_sds = _mask_sds(model)

    with jax.set_mesh(mesh):
        if shape.mode == "train":
            opt = opt_lib.adamw(lr=1e-4, grad_clip=1.0)
            tcfg = train_lib.TrainStepCfg(
                remat=remat, dp_axes=dp, fsdp=fsdp, loss_chunk=loss_chunk,
                seq_shard_acts=bool(int(os.environ.get(
                    "REPRO_SEQ_SHARD_ACTS", "0"))))
            step = train_lib.jit_train_step(model, opt, mesh, tcfg)
            state_sds = jax.eval_shape(
                lambda: train_lib.make_state(model, opt,
                                             jax.random.PRNGKey(0)))
            lowered = step.lower(state_sds, specs, mask_sds)
        elif shape.mode == "prefill":
            scfg = serve_lib.ServeCfg(dp_axes=dp, max_len=shape.seq_len,
                                      batch=shape.global_batch)
            jitted = serve_lib.jit_prefill(model, mesh, scfg,
                                           with_prefix=bool(cfg.prefix_len))
            params_sds = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0)))
            cache_sds = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            args = [params_sds, mask_sds, specs["tokens"], cache_sds]
            if cfg.prefix_len:
                args.append(specs["prefix_embeds"])
            lowered = jitted.lower(*args)
        else:  # decode
            scfg = serve_lib.ServeCfg(dp_axes=dp, max_len=shape.seq_len,
                                      batch=shape.global_batch)
            jitted = serve_lib.jit_decode_step(model, mesh, scfg)
            params_sds = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0)))
            cache_sds = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            lowered = jitted.lower(params_sds, mask_sds, specs["tokens"],
                                   cache_sds,
                                   jax.ShapeDtypeStruct((), jnp.int32))
    meta = {"arch": arch_id, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "chips": mesh.size, "mode": shape.mode}
    return lowered, meta, cfg, shape


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
             fsdp: bool = True, remat: bool = True, variant: str = "base",
             overrides: dict | None = None, loss_chunk: int = 0):
    """Lower + compile one (arch, shape, mesh) cell; return its JSON record
    (memory analysis, collectives, roofline terms) or a skip marker."""
    cfg = get_config(arch_id)
    ok, why = cell_applicable(cfg, shape_name)
    if not ok:
        return {"arch": arch_id, "shape": shape_name, "skipped": why}
    t0 = time.time()
    lowered, meta, cfg, shape = lower_cell(
        arch_id, shape_name, multi_pod=multi_pod, fsdp=fsdp, remat=remat,
        overrides=overrides, loss_chunk=loss_chunk)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = rl.xla_cost(compiled)
    hlo = compiled.as_text()
    g = cfg.remat_group if (meta["mode"] == "train"
                            and cfg.remat_group > 1) else 1
    coll = rl.parse_collectives(hlo, meta["chips"],
                                loop_trip_count=max(1, cfg.n_repeats // g))
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    an_flops, an_bytes = rl.analytic_cell(cfg, shape, meta["mode"],
                                          remat=remat)
    roof = rl.Roofline(
        arch=arch_id, shape=shape_name, mesh=meta["mesh"],
        chips=meta["chips"],
        device_kind="TPU v5 lite",   # the v5e pods make_production_mesh models
        flops_per_device=flops_dev,
        bytes_per_device=bytes_dev,
        collective_bytes_global=coll.bytes_moved_global,
        model_flops_global=rl.model_flops(cfg, shape, meta["mode"]),
        analytic_flops_global=an_flops, analytic_bytes_global=an_bytes)
    rec = dict(meta)
    rec.update({
        "variant": variant,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
        "peak_bytes_per_device": (
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)),
        "collectives": coll.counts,
        "collectives_in_loop": coll.in_loop_count,
        "collective_bytes_by_op": coll.bytes_by_op,
    })
    rec.update(roof.row())
    return rec


def main(argv=None):
    """CLI entry: run the selected dry-run cells and write their reports."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--variant", default="base")
    ap.add_argument("--moe-dispatch", default=None,
                    choices=[None, "scatter", "gather"])
    ap.add_argument("--remat-group", type=int, default=None)
    ap.add_argument("--loss-chunk", type=int, default=0)
    ap.add_argument("--out", default="reports/dryrun")
    args = ap.parse_args(argv)
    overrides = {}
    if args.moe_dispatch:
        overrides["moe_dispatch"] = args.moe_dispatch
    if args.remat_group:
        overrides["remat_group"] = args.remat_group

    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}.{shape}.{'2x16x16' if mp else '16x16'}" \
                      + ("" if args.variant == "base" else f".{args.variant}")
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[skip-cached] {tag}")
                    continue
                try:
                    rec = run_cell(arch, shape, multi_pod=mp,
                                   fsdp=not args.no_fsdp,
                                   remat=not args.no_remat,
                                   variant=args.variant,
                                   overrides=overrides or None,
                                   loss_chunk=args.loss_chunk)
                except Exception as e:  # noqa: BLE001
                    failures += 1
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-3000:]}
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                if "error" in rec:
                    print(f"[FAIL] {tag}: {rec['error']}")
                elif "skipped" in rec:
                    print(f"[skipped] {tag}: {rec['skipped']}")
                else:
                    print(f"[ok] {tag} compile={rec['compile_s']}s "
                          f"peak={rec['peak_bytes_per_device']/2**30:.2f}GiB "
                          f"dom={rec['bottleneck']} "
                          f"roofline={rec['roofline_fraction']:.3f}")
                sys.stdout.flush()
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
