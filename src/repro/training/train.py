"""Distributed train-step factory: pjit shardings, remat, loss, metrics.

``make_train_step`` builds the jitted step with explicit in/out shardings
(params/opt-state: FSDP×TP via models.lm.param_specs; batch: DP over
('pod','data'); masks: replicated).  The same factory serves the dry-run
(lower + compile on the 512-device mesh) and real training (CPU smoke runs).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import lm as lm_lib
from . import optimizer as opt_lib


def cross_entropy(logits, labels, valid=None):
    """Mean CE over valid positions.  logits (..., V) any dtype; labels int.

    SPMD-friendly: the gold logit is picked with a fused one-hot reduce
    (sharded-vocab safe — a take_along_axis gather would make GSPMD all-gather
    the logits), and logsumexp reduces partial max/sum per vocab shard.
    """
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    onehot = (iota == labels[..., None]).astype(lf.dtype)
    gold = jnp.sum(lf * onehot, axis=-1)
    nll = lse - gold
    if valid is None:
        return jnp.mean(nll)
    v = valid.astype(jnp.float32)
    return jnp.sum(nll * v) / jnp.maximum(jnp.sum(v), 1.0)


def quantize_grads_int8(grads):
    """Per-tensor symmetric int8 quantize→dequantize (gradient compression:
    models an 8-bit gradient all-reduce; numerics match what a compressed
    collective would deliver)."""
    def q(g):
        if g.ndim == 0 or g.size < 1024:
            return g
        scale = jnp.max(jnp.abs(g)) / 127.0 + 1e-12
        return (jnp.round(g / scale).astype(jnp.int8).astype(g.dtype) * scale)
    return jax.tree.map(q, grads)


@dataclasses.dataclass
class TrainStepCfg:
    """Train-step lowering knobs (remat, sharding axes, memory levers)."""

    remat: bool = True
    compress_grads: bool = False
    dp_axes: Tuple[str, ...] = ("data",)
    fsdp: bool = True
    model_axis: str = "model"      # logits vocab-sharding constraint
    loss_chunk: int = 0            # seq-chunked CE (0 = whole-sequence);
    # bounds live logits to (B, loss_chunk, V) — §Perf memory lever
    seq_shard_acts: bool = False   # shard the scan-carry (saved activation
    # stack) over 'model' along sequence — Megatron-SP-style memory lever


def make_state(model: lm_lib.LM, opt: opt_lib.Optimizer, key):
    """Fresh train state: params + optimizer moments + step counter."""
    params = model.init(key)
    return {"params": params, "opt": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}


def state_specs(model: lm_lib.LM, opt: opt_lib.Optimizer, data: int,
                model_ax: int, fsdp: bool = True):
    """PartitionSpec tree for the train state (opt moments follow params)."""
    pshapes = jax.eval_shape(lambda k: model.init(k), jax.random.PRNGKey(0))
    pspec = lm_lib.param_specs(pshapes, data, model_ax, fsdp)
    sstruct = jax.eval_shape(lambda: opt.init(pshapes))
    # mu mirrors params; nu mirrors params for AdamW, scalar for SGD
    same = (jax.tree_util.tree_structure(sstruct.nu)
            == jax.tree_util.tree_structure(pshapes))
    return {"params": pspec,
            "opt": opt_lib.OptState(P(), pspec, pspec if same else P()),
            "step": P()}


def make_train_step(model: lm_lib.LM, opt: opt_lib.Optimizer,
                    cfg: TrainStepCfg = TrainStepCfg()):
    """Returns train_step(state, batch, masks) -> (state, metrics)."""
    dp = cfg.dp_axes

    def loss_fn(params, masks, batch):
        tokens = batch["tokens"]
        pe = batch.get("prefix_embeds")
        S_text = tokens.shape[1]
        if cfg.loss_chunk and S_text % cfg.loss_chunk == 0:
            hidden, _ = model.forward(params, masks, tokens,
                                      prefix_embeds=pe, remat=cfg.remat,
                                      return_hidden=True)
            if pe is not None:
                hidden = hidden[:, pe.shape[1]:]
            B = hidden.shape[0]
            nch = S_text // cfg.loss_chunk
            hc = hidden.reshape(B, nch, cfg.loss_chunk, -1).swapaxes(0, 1)
            lc = batch["labels"].reshape(B, nch, cfg.loss_chunk).swapaxes(
                0, 1)
            embed_t = model.unembed(params)

            def body(tot, xs):
                h, lb = xs
                logits = h @ embed_t.astype(h.dtype)
                if cfg.dp_axes:
                    logits = jax.lax.with_sharding_constraint(
                        logits, P(cfg.dp_axes, None, cfg.model_axis))
                lf = logits.astype(jnp.float32)
                m = jax.lax.stop_gradient(jnp.max(lf, -1, keepdims=True))
                lse = jnp.log(jnp.sum(jnp.exp(lf - m), -1)) + m[..., 0]
                iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, 2)
                gold = jnp.sum(lf * (iota == lb[..., None]).astype(lf.dtype),
                               -1)
                return tot + jnp.sum(lse - gold), None

            total, _ = jax.lax.scan(jax.checkpoint(body),
                                    jnp.zeros((), jnp.float32), (hc, lc))
            return total / (B * S_text), None
        logits, _ = model.forward(params, masks, tokens, prefix_embeds=pe,
                                  remat=cfg.remat)
        if cfg.dp_axes:
            logits = jax.lax.with_sharding_constraint(
                logits, P(cfg.dp_axes, None, cfg.model_axis))
        if pe is not None:
            logits = logits[:, pe.shape[1]:]   # loss only on text positions
        loss = cross_entropy(logits, batch["labels"])
        return loss, logits

    def train_step(state, batch, masks):
        if dp:
            batch = {k: jax.lax.with_sharding_constraint(
                         v, P(dp, *([None] * (v.ndim - 1))))
                     for k, v in batch.items()}
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], masks, batch)
        if cfg.compress_grads:
            grads = quantize_grads_int8(grads)
        updates, opt_state = opt.update(grads, state["opt"], state["params"])
        params = opt_lib.apply_updates(state["params"], updates)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        new_state = {"params": params, "opt": opt_state,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


def jit_train_step(model, opt, mesh: Mesh, cfg: TrainStepCfg):
    """pjit'd train step with explicit shardings (used by dryrun + launch)."""
    data = mesh.shape["data"]
    model_ax = mesh.shape["model"]
    model.activation_spec = P(cfg.dp_axes,
                              cfg.model_axis if cfg.seq_shard_acts else None,
                              None)
    sspec = state_specs(model, opt, data, model_ax, cfg.fsdp)
    step = make_train_step(model, opt, cfg)
    in_shardings = (
        jax.tree.map(lambda s: NamedSharding(mesh, s), sspec,
                     is_leaf=lambda x: isinstance(x, P)),
        None,                            # batch: constrained inside
        NamedSharding(mesh, P()),        # masks: replicated
    )
    out_shardings = (
        jax.tree.map(lambda s: NamedSharding(mesh, s), sspec,
                     is_leaf=lambda x: isinstance(x, P)),
        None,
    )
    return jax.jit(step, in_shardings=in_shardings,
                   out_shardings=out_shardings, donate_argnums=(0,))


# ------------------------------------------------------------- CNN path


def make_cnn_train_step(model, opt):
    """Single-host CNN train step (the paper's reproduction scale)."""
    def loss_fn(params, masks, batch, soft=False):
        logits = model.forward(params, masks, batch["images"], soft=soft)
        loss = cross_entropy(logits, batch["labels"])
        acc = jnp.mean((jnp.argmax(logits, -1) == batch["labels"]
                        ).astype(jnp.float32)) * 100.0
        return loss, acc

    @jax.jit
    def step(params, opt_state, masks, batch):
        (loss, acc), grads = jax.value_and_grad(
            lambda p: loss_fn(p, masks, batch), has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return opt_lib.apply_updates(params, updates), opt_state, loss, acc

    return step, loss_fn


def make_eval_acc(forward: Callable, eval_batch: Dict):
    """jitted masks->accuracy[%] closure for BCD (masks are jit inputs:
    candidate evaluation never recompiles)."""
    @jax.jit
    def acc(params, masks):
        logits = forward(params, masks)
        return jnp.mean((jnp.argmax(logits, -1) == eval_batch["labels"]
                         ).astype(jnp.float32)) * 100.0
    return acc
