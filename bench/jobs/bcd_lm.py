"""BCD outer steps on a language model with latent attention and held
experts (DeepSeek-V2's block), driven through ``core.bcd.bcd_steps`` with
the candidate engine the cell names, as ``jobs/bcd.py`` drives a CNN.

Set-up makes from the configuration's ``data_seed`` the eval batch and a
pool of finetune sequences (Markov tokens over the vocabulary slice), from
the run seed the weights and the starting masks, and the labels: the
all-live model's top-1 at every position (by the program's own forward),
or, where the starting masks agree with those on a share of the eval
tokens outside ``label_share`` (so that candidates would barely differ),
the top-1 under the starting masks.  The accuracy is the share of the eval
batch's tokens whose top-1 is their label; the finetune minimizes the
cross entropy against the labels.

Correctness is ``jobs/bcd.py``'s, counted in tokens of the eval batch
(``acc_gap_tokens`` in place of ``acc_gap_images``).  The parameters that
the sampled steps keep for it (2 GB each at published widths) are copied to
the host while the next step runs, so that the device holds no more of them
than the step itself does.

A traced run also reads, after the window, the rows routed to each held
expert (``LM.held_expert_rows``, recorded through ``core.tracing`` as
``moe.rows_held`` and ``moe.rows_max``) under each traced step's base
masks, and the compiled programs' HLO text that ``bench/lib/scopes.py``
needs to put device time down to the program's named scopes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.jobs import bcd as base
from bench.lib import lm_flops


def program_config(cfg: dict):
    """The program's configuration (``repro.configs``, named by
    ``program_config``) with every size the benchmark's file gives, cut
    to this chip's share: the file's expert layers, its experts held and
    its vocabulary slice."""
    from repro.configs import get_config
    from repro.configs.base import Yarn
    ways = cfg["router_experts"] // cfg["n_routed_experts"]
    y = cfg["rope_scaling"]
    arch = dataclasses.replace(
        get_config(cfg["program_config"]), dtype=cfg["dtype"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], d_ff=cfg["intermediate_size"],
        d_ff_expert=cfg["moe_intermediate_size"],
        d_ff_shared=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        norm_topk=cfg["norm_topk_prob"], vocab=cfg["vocab_size"] * ways,
        yarn=Yarn(factor=y["factor"],
                  original_max_position=y["original_max_position_embeddings"],
                  beta_fast=y["beta_fast"], beta_slow=y["beta_slow"],
                  mscale_all_dim=y["mscale_all_dim"]))
    return arch.chip_share(
        moe_layers=lm_flops.n_moe(cfg), ways=ways,
        shard=cfg["expert_offset"] // cfg["n_routed_experts"])


def _host(tree):
    return jax.tree.map(np.asarray, tree)


class Engine(base.Engine):
    """``jobs/bcd.py``'s evaluator wrapper, counting per step each chunk's
    cut (the segment its candidates resume from) and size, and the cut
    sites the engine took."""

    def _count(self, item) -> None:
        job = self._job
        site = getattr(item, "site", None)
        stacked = getattr(item, "stacked", item)
        n = len(next(iter(stacked.values())))
        job.cur["n_cand"] += n
        if site is not None:
            job.cur["n_suffix"] += n
            job.sites.add(site)
            cuts = [job.segment[site]] * n
        else:
            cuts = job.earliest_segments(stacked)
        job.cur["cands"].extend(cuts)
        job.cur["chunks"].append((min(cuts, key=job.seg_order.index),
                                  max(n, self._inner._pad_to or 0)))
        if job.recorded is not None:
            job.recorded.pending.append(job.edits(stacked))


class Job(base.Job):
    def __init__(self, ctx):
        from repro.core import bcd, snl
        from repro.launch import sweep
        from repro.models.lm import LM
        from repro.training import train as train_lib

        self.ctx, self.rec, self.tracing = ctx, ctx.rec, ctx.tracing
        cfg, wl = ctx.config, ctx.workload
        self.cfg, self.wl = cfg, wl
        self.snl = snl
        ref = ctx.reference
        self.net = ref.Net(cfg, cfg["reference_precision"])
        self.model = LM(program_config(cfg))
        shapes = ref.site_shapes(cfg)
        prog = {k: s.shape for k, s in self.model.mask_sites().items()}
        if prog != shapes:
            raise RuntimeError(f"program mask sites {prog} differ from the "
                               f"reference's {shapes}")
        self.shapes = shapes
        self.keys = sorted(shapes)
        self.offsets = np.cumsum([0] + [int(np.prod(shapes[k]))
                                        for k in self.keys])
        self.site_of = np.repeat(np.arange(len(self.keys)),
                                 np.diff(self.offsets))
        self.site_order = list(shapes)            # forward order
        L0 = cfg["first_k_dense_replace"]
        self.segment = {s: "layer" + s[1:s.index(".")]
                        for s in self.site_order if s.startswith("h")}
        for s in self.site_order:
            if s.startswith("s"):
                for r in range(lm_flops.n_moe(cfg)):
                    self.segment[f"{s}@{r}"] = f"layer{L0 + r}"
        self.seg_order = [n for n, _ in lm_flops.segments(cfg, 1)]
        E, S = cfg["eval_batch"], cfg["eval_len"]
        self.n_eval = E * S
        rng = np.random.default_rng(ctx.seed)
        self.check_steps = sorted({int(rng.integers(lo, hi))
                                   for lo, hi in wl["check_steps"]})
        masks0 = base.start_masks(shapes, wl["alive"], rng)
        masks0_dev = {k: jnp.asarray(v) for k, v in masks0.items()}

        FB, FS = cfg["finetune_batch"], cfg["finetune_len"]
        N = cfg["n_train"]

        @jax.jit
        def make(kd, kw):
            return (ref.make_tokens(cfg, jax.random.fold_in(kd, 0), E, S),
                    ref.make_tokens(cfg, jax.random.fold_in(kd, 1), N, FS),
                    ref.init_params(cfg, kw))
        tokens, pool, params = jax.block_until_ready(make(
            base._key(cfg["data_seed"]), base._key(ctx.seed)))

        top1 = jax.jit(lambda p, m, t: jnp.argmax(
            self.model.forward(p, m, t)[0], -1).astype(jnp.int32))
        ones = {k: jnp.ones_like(v) for k, v in masks0_dev.items()}
        labels = top1(params, ones, tokens)
        share = 100.0 * float(jnp.mean(top1(params, masks0_dev, tokens)
                                       == labels))
        lo, hi = cfg["label_share"]
        self.label_masks = "all live"
        label_m = ones
        if not lo <= share <= hi:
            self.label_masks, label_m = "starting", masks0_dev
            labels = top1(params, label_m, tokens)
        pool_labels = jax.lax.map(
            lambda t: top1(params, label_m, t),
            pool.reshape(N // FB, FB, FS)).reshape(N, FS)
        print(f"[bcd_lm] labels: top-1 under the {self.label_masks} masks "
              f"(the starting masks agree with the all-live top-1 on "
              f"{share:.2f}% of the eval tokens)", flush=True)
        self.pool = {"tokens": pool, "labels": pool_labels}
        self.n_batches = N // FB
        self._slice = jax.jit(lambda a, j: jax.lax.dynamic_slice_in_dim(
            a, j * FB, FB))
        self.eval_b = {"tokens": tokens, "labels": labels}
        self.holder = {"params": params}

        def sloss(p, a, batch, soft):
            logits, _ = self.model.forward(p, a, batch["tokens"], soft=soft)
            return train_lib.cross_entropy(logits, batch["labels"]), 0.0
        self.sloss = sloss

        inner, eval_acc, self.set_ctx = sweep.make_bcd_evaluator(
            wl["engine"], self.model, self.eval_b, self.holder,
            chunk_size=wl["chunk_size"], rt=wl["rt"])
        self._eval_acc = eval_acc
        self.sites = set()
        self.engine = Engine(inner, self)
        self.bcfg = bcd.BCDConfig(
            b_target=wl["b_target"], drc=wl["drc"], rt=wl["rt"],
            adt=wl["adt"], seed=ctx.seed, chunk_size=wl["chunk_size"],
            moves=tuple(wl["moves"]), proposal=wl["proposal"])
        self.state = bcd.init_state(masks0, self.bcfg)
        self.gen = bcd.bcd_steps(self.state, self.bcfg, self.eval_acc,
                                 self.finetune, evaluator=self.engine)
        self.ft_calls = 0
        self.steps: List[dict] = []
        self.recorded: Optional[base._Recorded] = None
        self.records: List[base._Recorded] = []
        self._n = 0               # steps run, warm-up included
        self._to_host: List[tuple] = []   # (due step, finish the copy)
        self.prev_params = None
        self.cur = self._new_step()
        for _ in range(wl["warmup_steps"]):
            self._step()
        self.steps.clear()
        self.sites.clear()

    # --------------------------------------------------------- callbacks

    def batches(self, i: int) -> dict:
        j = i % self.n_batches
        return {k: self._slice(v, j) for k, v in self.pool.items()}

    # ------------------------------------------------------------- steps

    def _new_step(self) -> dict:
        return {"n_cand": 0, "n_suffix": 0, "cands": [], "chunks": []}

    def earliest_segments(self, stacked) -> List[str]:
        """The segment (layer) of each candidate's earliest edit; an
        expert-stack site's rows are its layers."""
        base_m = self._base_now
        n = len(next(iter(stacked.values())))
        first = [len(self.seg_order)] * n
        L0 = self.cfg["first_k_dense_replace"]
        for site in self.site_order:
            a = np.asarray(stacked[site]).reshape((n,) + self.shapes[site])
            b = np.asarray(base_m[site])
            if site.startswith("s"):      # rows of a stack site: its layers
                diff = np.any((a != b[None]).reshape(n, a.shape[1], -1), 2)
                for i in np.flatnonzero(diff.any(1)):
                    first[i] = min(first[i], L0 + int(diff[i].argmax()))
            else:
                seg = self.seg_order.index(self.segment[site])
                for i in np.flatnonzero(np.any((a != b[None]).reshape(n, -1),
                                               1)):
                    first[i] = min(first[i], seg)
        return [self.seg_order[min(f, len(self.seg_order) - 1)]
                for f in first]

    def _step(self) -> dict:
        self._flush_host(self._n)
        self._n += 1
        k = len(self.steps)
        sampled = k in self.check_steps and self.recording
        base_m = self.state.masks
        self._base_now = base_m
        params, prev = self.holder["params"], self.prev_params
        self.prev_params = None
        # the next window step is sampled: keep this step's parameters (on
        # the host) for its stale-carry stand-in
        nxt = k + 1 if self.recording else (
            0 if k == self.wl["warmup_steps"] - 1 else None)
        if nxt in self.check_steps:
            self._copy(params, lambda t: setattr(self, "prev_params", t),
                       due=self._n)
        if sampled:
            r = self.recorded = base._Recorded(
                base._flat(base_m, self.keys), params, prev)
            self._copy(params, lambda t, r=r: (
                setattr(r, "params", t), r.ft.update(before=t)),
                due=self._n)
        self.cur = self._new_step()
        t0 = time.perf_counter()
        n_spans = len(self.rec.spans)
        with self.rec.span("step"):
            try:
                next(self.gen)
            except StopIteration:
                raise RuntimeError(
                    "the BCD schedule ended inside the window: lower the "
                    "cell's b_target") from None
        t1 = time.perf_counter()
        spans = self.rec.spans[n_spans:]
        in_spans = lambda pred: sum(e - s for n, s, e in spans if pred(n))
        rec = dict(self.cur, t0=t0, t1=t1,
                   engine_s=in_spans(lambda n: n.startswith("engine.")),
                   finetune_s=in_spans(lambda n: n == "finetune"),
                   eval_s=in_spans(lambda n: n == "eval"))
        if self.tracing and self.recording:
            rec["base"] = {kk: np.array(v) for kk, v in base_m.items()}
        if sampled:
            r = self.recorded
            r.new_flat = base._flat(self.state.masks, self.keys)
            idx = np.flatnonzero(r.new_flat != r.base_flat)
            r.selected = (idx, r.new_flat[idx])
            self._copy(r.ft["after"], lambda t, r=r: r.ft.update(after=t))
            self.records.append(r)
            self.recorded = None
        self.steps.append(rec)
        return rec

    def _copy(self, tree, done, due=None) -> None:
        """Start copying ``tree`` to the host; at the start of step
        ``due`` (default: the one after the next, when the copy has had a
        whole step to finish) hand the host copy to ``done`` and drop the
        device arrays.  The trees copied are the live parameters of this
        step or the next, so the device holds none of them longer than the
        steps do."""
        jax.tree.map(lambda a: a.copy_to_host_async(), tree)
        self._to_host.append((self._n + 1 if due is None else due, tree,
                              done))

    def _flush_host(self, now=None) -> None:
        keep = []
        for due, tree, done in self._to_host:
            if now is None or due <= now:
                done(_host(tree))
            else:
                keep.append((due, tree, done))
        self._to_host = keep

    def run(self, win) -> None:
        self.win = win
        super().run(win)

    def release(self) -> None:
        self._flush_host()
        super().release()

    def after_window(self) -> None:
        super().after_window()
        if self.tracing:
            self._read_programs()

    def _read_programs(self) -> None:
        """Held-expert rows of the traced steps, and the HLO text of the
        programs the window ran (for ``bench/lib/scopes.py``)."""
        from repro.core import tracing
        lo, hi = self.win.traced
        traced = [s for s in self.steps if lo <= s["t0"] and s["t1"] <= hi]
        params = self.holder["params"]
        rows_fn = jax.jit(self.model.held_expert_rows)
        with tracing.recording() as rec:
            for s in traced:
                rows = np.asarray(rows_fn(params, {
                    k: jnp.asarray(v) for k, v in s["base"].items()},
                    self.eval_b["tokens"]))
                tracing.count("moe.rows_held", int(rows.sum()))
                tracing.count("moe.rows_max", int(rows.max()))
                s["rows"] = rows
        self.rows_counts = dict(rec.counts)
        self.hlo_texts = self._hlo_texts(params)

    def _hlo_texts(self, params) -> List[str]:
        """Optimized HLO of the window's programs: the engine's suffix and
        prefix programs of every cut it took, the evaluation and the
        finetune's step, compiled again (the persistent cache holds them)
        for their text."""
        from repro.core import masks as M
        ev = self.engine._inner
        base_dev = ev._base_masks_dev()
        texts = []
        for site in sorted(self.sites):
            sub = {k: jax.ShapeDtypeStruct(
                (ev._pad_to,) + self.shapes[k], jnp.float32)
                for k in ev._split.suffix_sites(site)}
            cached = ev._prefix_for(site)
            texts.append(ev._suffix_for(site).lower(
                sub, cached, ev.context).compile().as_text())
            seg = ev._split.site_segment[site]
            pj = ev._prefix_jits.get(seg)
            if pj is not None:
                texts.append(pj.lower(base_dev, ev.context).compile()
                             .as_text())
        masks = M.as_device(self.state.masks)
        texts.append(jax.jit(self.model.make_param_eval_fn(self.eval_b))
                     .lower(masks, params).compile().as_text())
        steps, lr = self.cfg["finetune_steps"], self.cfg["finetune_lr"]
        ostate = self.snl._finetune_opt(lr, steps, False).init(params)
        texts.append(self.snl._finetune_step.lower(
            params, ostate, self.batches(0), masks, loss_fn=self.sloss,
            lr=lr, steps=steps, use_adam=False).compile().as_text())
        return texts

    def end_to_end(self, win) -> dict:
        return {"bcd_step_s": win.elapsed / len(self.steps)}

    # ------------------------------------------------------------- check

    def _tree(self, flat: np.ndarray) -> Dict[str, jnp.ndarray]:
        return {k: jnp.asarray(flat[self.offsets[i]:self.offsets[i + 1]]
                               .reshape(self.shapes[k]))
                for i, k in enumerate(self.keys)}

    def _ft_batches(self, start: int, half: bool = False) -> List[tuple]:
        out = []
        for i in range(self.cfg["finetune_steps"]):
            b = self.batches(start + i)
            n = b["tokens"].shape[1] // 2 if half else b["tokens"].shape[1]
            out.append((b["tokens"][:, :n], b["labels"][:, :n]))
        return out

    def _count_right(self, params, flat, dtype=jnp.float32,
                     half: bool = False) -> float:
        """Eval tokens whose top-1 under the reference in ``dtype`` is
        their label; with ``half`` over the first sequence only, scaled to
        the whole batch."""
        t, lab = self.eval_b["tokens"], self.eval_b["labels"]
        n = 1 if half else len(t)
        right = int(self.net.correct(params, self._tree(flat), t[:n],
                                     lab[:n], dtype=dtype))
        return right * len(t) / n

    def readings(self, r, prog: dict, ref: dict) -> dict:
        out = super().readings(r, prog, ref)
        out["acc_gap_tokens"] = out.pop("acc_gap_images")
        return out

    def _first_layer(self, idx: np.ndarray) -> int:
        """The earliest layer that an edit of flat coordinates ``idx``
        touches."""
        L0 = self.cfg["first_k_dense_replace"]
        out = len(self.seg_order)
        for i in np.unique(self.site_of[idx]):
            k = self.keys[i]
            if k.startswith("s"):
                local = idx[self.site_of[idx] == i] - self.offsets[i]
                per = int(np.prod(self.shapes[k][1:]))
                out = min(out, L0 + int(local.min()) // per)
            else:
                out = min(out, self.seg_order.index(self.segment[k]))
        return out

    def stale_params(self, r, cut: int):
        """The parameters with which an engine that keeps the step
        before's prefix scores a candidate whose earliest edit is in layer
        ``cut``: the embedding and every layer before ``cut`` from the step
        before."""
        L0 = self.cfg["first_k_dense_replace"]
        p, old = r.params, r.prev_params
        out = dict(p, embed=old["embed"],
                   head=[old["head"][i] if i < cut else p["head"][i]
                         for i in range(L0)])
        rows = max(cut - L0, 0)
        out["stack"] = {"0": jax.tree.map(
            lambda a, b: np.concatenate([b[:rows], a[rows:]]),
            p["stack"]["0"], old["stack"]["0"])}
        return out

    def reference_cands(self, r, dtype=jnp.float32, half: bool = False,
                        stale: bool = False) -> np.ndarray:
        params = {None: jax.device_put(r.params)}
        cands = []
        for idx, vals, _ in r.cands:
            m = r.base_flat.copy()
            m[idx] = vals
            cut = self._first_layer(idx) if stale else None
            if cut not in params:
                params = {None: params[None], cut: jax.device_put(
                    self.stale_params(r, cut))}
            cands.append(self._count_right(params[cut], m, dtype, half))
        return np.array(cands)

    def reference_outputs(self, r, dtype=jnp.float32,
                          half: bool = False) -> dict:
        after, grad = self.net.finetune(
            jax.device_put(r.ft["before"]), self._tree(r.ft["masks"]),
            self._ft_batches(r.ft["start"], half), self.cfg["finetune_lr"],
            dtype=dtype)
        n_after = self._count_right(after, r.ft["masks"], dtype, half)
        after, grad = _host(after), _host(grad)
        cands = self.reference_cands(r, dtype, half)
        return {"base": self._count_right(jax.device_put(r.params),
                                          r.base_flat, dtype, half),
                "cands": cands, "after": n_after,
                "params_after": after, "grad": grad,
                "selected": int(np.argmax(cands))}

    def step_readings(self, stand_in: Optional[str] = None) -> List[dict]:
        """``jobs/bcd.py``'s, with the altered answer moved by 10 points
        of the eval batch's tokens."""
        if stand_in != "answer_altered":
            return super().step_readings(stand_in)
        out = []
        for r in self.records:
            if r.ref is None:
                r.ref = self.reference_outputs(r)
            prog = self.program_outputs(r)
            prog["cands"][len(prog["cands"]) // 2] += 0.1 * self.n_eval
            out.append(self.readings(r, prog, r.ref))
        return out

    def program_outputs(self, r) -> dict:
        n = self.n_eval / 100.0
        if len(r.evals) != 2 or r.ft is None:
            raise RuntimeError(f"sampled step recorded {len(r.evals)} "
                               "evaluations and no finetune")
        return {"base": r.evals[0] * n,
                "cands": np.array([a * n for _, _, a in r.cands]),
                "after": r.evals[1] * n,
                "params_after": r.ft["after"],
                "selected": self._selected_index(r)}

