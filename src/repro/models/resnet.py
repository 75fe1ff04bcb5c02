"""The paper's backbones: CIFAR-style ResNet18 and WideResNet-22-8.

Every ReLU is a mask site with the *full per-pixel activation shape*
(H, W, C), shared across the batch — exactly the paper's mask granularity
(ResNet18 @32×32 ≈ 557K ReLUs; the paper's Table 1 says 570K — the delta is
the counting convention for the stem ReLU, documented in EXPERIMENTS.md).

BatchNorm uses batch statistics in both train and eval (synthetic-data
reproduction; see DESIGN §7).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.core import linearize, masks as M


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _conv_init(key, kh, kw, cin, cout):
    fan = kh * kw * cin
    return jax.random.normal(key, (kh, kw, cin, cout)) * (2.0 / fan) ** 0.5


def _bn_init(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def _bn(p, x, eps=1e-5):
    mean = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.var(x, axis=(0, 1, 2), keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str
    n_classes: int
    image_size: int
    # (channels, n_blocks, stride) per stage
    stages: Tuple[Tuple[int, int, int], ...]
    stem_channels: int
    wide: bool = False          # WRN pre-activation blocks

    @staticmethod
    def resnet18(n_classes=10, image_size=32):
        return CNNConfig("resnet18", n_classes, image_size,
                         ((64, 2, 1), (128, 2, 2), (256, 2, 2), (512, 2, 2)),
                         stem_channels=64)

    @staticmethod
    def wrn22_8(n_classes=10, image_size=32):
        return CNNConfig("wrn22_8", n_classes, image_size,
                         ((128, 3, 1), (256, 3, 2), (512, 3, 2)),
                         stem_channels=16, wide=True)


class CNN:
    """Masked-ReLU CNN.  API mirrors models.lm.LM where it matters."""

    def __init__(self, cfg: CNNConfig):
        self.cfg = cfg
        self._site_shapes = self._compute_site_shapes()
        self._segs = self._build_segments()
        self._seg_of_site = {s: i for i, (_, sites, _) in
                             enumerate(self._segs) for s in sites}

    # ---------------------------------------------------------- structure

    def _block_plan(self):
        """Yields (stage, block, cin, cout, stride, hw) tuples."""
        cfg = self.cfg
        hw = cfg.image_size
        cin = cfg.stem_channels
        for si, (cout, n, stride) in enumerate(cfg.stages):
            for bi in range(n):
                s = stride if bi == 0 else 1
                hw_out = hw // s
                yield si, bi, cin, cout, s, hw_out
                cin, hw = cout, hw_out

    def _compute_site_shapes(self):
        cfg = self.cfg
        shapes: Dict[str, Tuple[int, ...]] = {}
        if not cfg.wide:
            shapes["stem.relu"] = (cfg.image_size, cfg.image_size,
                                   cfg.stem_channels)
        for si, bi, cin, cout, s, hw in self._block_plan():
            if cfg.wide:
                hw_in = hw * s
                shapes[f"g{si}b{bi}.relu1"] = (hw_in, hw_in, cin)
                shapes[f"g{si}b{bi}.relu2"] = (hw, hw, cout)
            else:
                shapes[f"g{si}b{bi}.relu1"] = (hw, hw, cout)
                shapes[f"g{si}b{bi}.relu2"] = (hw, hw, cout)
        if cfg.wide:
            hw_f = cfg.image_size // 4
            shapes["final.relu"] = (hw_f, hw_f, cfg.stages[-1][0])
        return shapes

    def mask_sites(self) -> Dict[str, linearize.MaskSite]:
        return {k: linearize.MaskSite(v, "relu")
                for k, v in self._site_shapes.items()}

    def relu_count(self) -> int:
        return sum(int(jnp.prod(jnp.asarray(s)))
                   for s in self._site_shapes.values())

    # ---------------------------------------------------------- params

    def init(self, key):
        cfg = self.cfg
        p = {"stem": {"conv": _conv_init(jax.random.fold_in(key, 0), 3, 3, 3,
                                         cfg.stem_channels),
                      "bn": _bn_init(cfg.stem_channels)}}
        for si, bi, cin, cout, s, hw in self._block_plan():
            k = jax.random.fold_in(key, 100 + si * 10 + bi)
            blk = {"conv1": _conv_init(jax.random.fold_in(k, 1), 3, 3, cin,
                                       cout),
                   "bn1": _bn_init(cin if cfg.wide else cout),
                   "conv2": _conv_init(jax.random.fold_in(k, 2), 3, 3, cout,
                                       cout),
                   "bn2": _bn_init(cout)}
            if s != 1 or cin != cout:
                blk["proj"] = _conv_init(jax.random.fold_in(k, 3), 1, 1, cin,
                                         cout)
            p[f"g{si}b{bi}"] = blk
        cfinal = cfg.stages[-1][0]
        if cfg.wide:
            p["final_bn"] = _bn_init(cfinal)
        p["fc"] = {"w": jax.random.normal(jax.random.fold_in(key, 7),
                                          (cfinal, cfg.n_classes))
                   * cfinal ** -0.5,
                   "b": jnp.zeros((cfg.n_classes,))}
        return p

    # ---------------------------------------------------------- forward
    #
    # The forward is a fold over an ordered *segment* list.  Each segment is
    # (name, sites_it_applies, fn(params, masks, x, poly, soft) -> x); the
    # full forward, forward_prefix, and forward_suffix all fold the same
    # list, so the split-forward contract
    #     forward_suffix(p, m, forward_prefix(p, m, x, site), site)
    #         == forward(p, m, x)
    # holds bitwise *by construction* — prefix/suffix trace exactly the
    # primitives forward traces (core.engine.SuffixEvaluator relies on it).

    def _relu(self, x, masks, name, poly, soft):
        site = linearize.MaskSite(self._site_shapes[name], "relu")
        return linearize.apply_masked_act(
            x, masks[name], site,
            poly=None if poly is None else poly.get(name), soft=soft)

    def _relu_conv(self, x, masks, name, ply, soft, w):
        """Masked ReLU at ``name`` feeding a stride-1 3x3 conv.  Under
        ``linearize.fused_suffix_route`` (the suffix engine traces its
        suffix jits with it armed) hard-mask sites run gate + conv as one
        Pallas megakernel (``kernels.ops.masked_act_conv3x3_routed``) — the
        gated tensor stays in VMEM instead of round-tripping HBM between
        two dispatches.  Everywhere else (CPU, soft relaxation, poly2
        replacement) it is the plain unfused pair."""
        p = None if ply is None else ply.get(name)
        mode = linearize.fused_route_mode()
        if mode is not None and not soft and p is None:
            from repro.kernels import ops
            interpret = mode == "interpret"
            if interpret or ops.fused_dispatch_enabled():
                return ops.masked_act_conv3x3_routed(
                    x, masks[name], w, kind="relu", interpret=interpret)
        return _conv(self._relu(x, masks, name, ply, soft), w)

    def _stem_pre(self, p, x):
        """Mask-independent stem fold: input -> the first gate's
        pre-activation (conv [+ bn]).  Depends only on (params, images), so
        evaluator backends compute it ONCE per context (``forward_pre``)
        and every candidate's full forward starts from the cached result
        (``forward(..., pre=...)``) instead of re-tracing it."""
        if self.cfg.wide:
            return _conv(x, p["stem"]["conv"])
        return _bn(p["stem"]["bn"], _conv(x, p["stem"]["conv"]))

    def _stem_gate(self, p, m, x, ply, soft):
        """The mask-dependent remainder of the stem segment (no-op for the
        wide config, whose first gate lives in g0b0)."""
        if self.cfg.wide:
            return x
        return self._relu(x, m, "stem.relu", ply, soft)

    def _build_segments(self):
        cfg = self.cfg
        segs = []
        # stem = _stem_gate(_stem_pre(x)): the same two folds forward's
        # pre= entry composes, so full-with-pre traces exactly the
        # primitives full-from-images traces (bitwise selection contract)
        segs.append(("stem", () if cfg.wide else ("stem.relu",),
                     lambda p, m, x, ply, soft:
                     self._stem_gate(p, m, self._stem_pre(p, x), ply, soft)))
        for si, bi, cin, cout, s, hw in self._block_plan():
            name = f"g{si}b{bi}"
            if cfg.wide:
                def blk_fn(p, m, x, ply, soft, name=name, s=s):
                    blk = p[name]
                    # relu1's output feeds both conv1 and the projection
                    # shortcut, so only relu2 -> conv2 (single consumer)
                    # is fusable
                    h = self._relu(_bn(blk["bn1"], x), m,
                                   f"{name}.relu1", ply, soft)
                    y = _conv(h, blk["conv1"], s)
                    y = self._relu_conv(_bn(blk["bn2"], y), m,
                                        f"{name}.relu2", ply, soft,
                                        blk["conv2"])
                    sc = _conv(h, blk["proj"], s) if "proj" in blk else x
                    return y + sc
            else:
                def blk_fn(p, m, x, ply, soft, name=name, s=s):
                    blk = p[name]
                    y = self._relu_conv(_bn(blk["bn1"], _conv(x, blk["conv1"],
                                                              s)),
                                        m, f"{name}.relu1", ply, soft,
                                        blk["conv2"])
                    y = _bn(blk["bn2"], y)
                    sc = _conv(x, blk["proj"], s) if "proj" in blk else x
                    return self._relu(y + sc, m, f"{name}.relu2", ply, soft)
            segs.append((name, (f"{name}.relu1", f"{name}.relu2"), blk_fn))

        def head_fn(p, m, x, ply, soft):
            if cfg.wide:
                x = self._relu(_bn(p["final_bn"], x), m, "final.relu",
                               ply, soft)
            x = jnp.mean(x, axis=(1, 2))
            return x @ p["fc"]["w"] + p["fc"]["b"]
        segs.append(("head", ("final.relu",) if cfg.wide else (), head_fn))
        return segs

    def forward(self, params, masks, images, *, poly=None, soft=False,
                pre=None):
        """Full forward.  ``pre``: a cached :meth:`forward_pre` result —
        the fold resumes at the first gate and ``images`` is ignored
        (evaluator contexts carry the pre-activation so per-candidate work
        skips the mask-independent stem)."""
        if pre is not None:
            x = self._stem_gate(params, masks, pre, poly, soft)
            segs = self._segs[1:]
        else:
            x = images
            segs = self._segs
        for _, _, fn in segs:
            x = fn(params, masks, x, poly, soft)
        return x

    def forward_pre(self, params, images):
        """Mask-independent head of the network (input -> first gate's
        pre-activation).  Computed once per evaluator context and fed back
        through ``forward(..., pre=...)`` — the "depth-0 prefix" every
        candidate shares regardless of which masks it mutates."""
        return self._stem_pre(params, images)

    # ------------------------------------------------------- split forward
    #
    # BCD candidates are local mask edits: a candidate whose earliest
    # touched site sits in segment k shares everything before segment k with
    # the base masks.  forward_prefix computes that shared part once;
    # forward_suffix finishes the net from the cached activation.  ``site``
    # is a Python-level (static) argument — the engine jits one suffix per
    # cut segment.

    def site_order(self) -> Tuple[str, ...]:
        """All mask sites in forward (topological) order."""
        return tuple(s for _, sites, _ in self._segs for s in sites)

    def site_segments(self) -> Dict[str, int]:
        """site name -> index of the segment that applies it.  Sites that
        share a segment share a prefix (and a suffix jit cache entry)."""
        return dict(self._seg_of_site)

    def suffix_sites(self, site: str) -> Tuple[str, ...]:
        """The sites forward_suffix(site) consumes: every site applied by
        the cut segment or later (the candidate mask values the suffix
        evaluator must ship per candidate)."""
        cut = self._seg_of_site[site]
        return tuple(s for _, sites, _ in self._segs[cut:] for s in sites)

    def forward_prefix(self, params, masks, images, site, *, poly=None,
                       soft=False, from_site=None, cached=None):
        """Run forward up to (excluding) the segment that applies ``site``;
        returns the cached boundary activation (the suffix's input).

        Multi-depth entry: ``from_site``/``cached`` resume from an earlier
        prefix instead of the input — folding only the segments in
        ``[seg(from_site), seg(site))``, so
        ``forward_prefix(..., site=b, from_site=a, cached=prefix(a))``
        computes exactly ``forward_prefix(..., site=b)`` (same fold over the
        same segment list — the prefix-trie extension contract)."""
        lo = 0
        x = images
        if from_site is not None:
            lo = self._seg_of_site[from_site]
            x = cached
        for _, _, fn in self._segs[lo:self._seg_of_site[site]]:
            x = fn(params, masks, x, poly, soft)
        return x

    def forward_suffix(self, params, masks, cached, site, *, poly=None,
                       soft=False):
        """Finish forward from a :meth:`forward_prefix` cache: folds the
        segment applying ``site`` and everything after it to logits."""
        x = cached
        for _, _, fn in self._segs[self._seg_of_site[site]:]:
            x = fn(params, masks, x, poly, soft)
        return x

    def _segment_flops(self) -> List[float]:
        """Per-sample forward FLOPs per segment (conv + fc terms only —
        the >99% of the work; used by the suffix cost model)."""
        cfg = self.cfg
        flops = [0.0] * len(self._segs)
        seg_idx = {name: i for i, (name, _, _) in enumerate(self._segs)}
        flops[seg_idx["stem"]] = (
            2.0 * 9 * 3 * cfg.stem_channels * cfg.image_size ** 2)
        for si, bi, cin, cout, s, hw in self._block_plan():
            f = 2.0 * 9 * cin * cout * hw ** 2          # conv1 (stride s)
            f += 2.0 * 9 * cout * cout * hw ** 2        # conv2
            if s != 1 or cin != cout:
                f += 2.0 * cin * cout * hw ** 2         # 1x1 proj
            flops[seg_idx[f"g{si}b{bi}"]] += f
        flops[seg_idx["head"]] = 2.0 * cfg.stages[-1][0] * cfg.n_classes
        return flops

    def site_prefix_fractions(self) -> Dict[str, float]:
        """site -> fraction of full-forward FLOPs strictly before its
        segment.  0.0 for first-segment sites (suffix mode buys nothing),
        approaching 1.0 for the deepest sites — the suffix cost model
        (core.engine.SuffixCostModel) thresholds on this."""
        seg_flops = self._segment_flops()
        total = max(sum(seg_flops), 1.0)
        cum = 0.0
        before = []
        for f in seg_flops:
            before.append(cum / total)
            cum += f
        return {s: before[i] for s, i in self._seg_of_site.items()}

    # ------------------------------------------------------- eval closures
    #
    # BCD's candidate-evaluation engine (core.engine) needs two views of
    # "accuracy under a mask tree": a *traceable* single-tree closure that
    # the full-forward evaluator loops or vmaps over the candidate axis, and
    # a plain host callable for the sequential reference / per-step base accs.

    def make_param_eval_fn(self, batch):
        """Traceable ``(mask_tree, params) -> accuracy[%]`` — for evaluator
        backends whose params change between BCD outer steps (finetuning):
        params ride as a jit input / evaluator context, never a baked
        closure constant."""
        images = jnp.asarray(batch["images"])
        labels = jnp.asarray(batch["labels"])

        def eval_fn(masks, params):
            logits = self.forward(params, masks, images)
            return jnp.mean((jnp.argmax(logits, -1) == labels)
                            .astype(jnp.float32)) * 100.0
        return eval_fn

    def make_eval_fn(self, params, batch):
        """Traceable ``mask_tree -> accuracy[%]`` closure over a fixed
        (params, batch).  Masks are traced inputs — safe under jit/vmap,
        never recompiles across candidates."""
        fn = self.make_param_eval_fn(batch)
        return lambda masks: fn(masks, params)

    def make_joint_eval_fn(self):
        """Traceable ``(mask_tree, ctx) -> accuracy[%]`` with
        ``ctx = {"params": ..., "batch": ...}`` — params AND the eval batch
        ride as evaluator context (jit inputs), so a PipelinedEvaluator on
        a ``("cand", "batch")`` mesh (``launch.mesh.make_cand_batch_mesh``)
        can lay the batch axis across the ``"batch"`` devices while the
        candidate axis shards over ``"cand"``: the joint layout that keeps
        every device busy when a trial chunk has fewer candidates than the
        mesh has devices."""
        def eval_fn(masks, ctx):
            batch = ctx["batch"]
            # "pre" (optional): the mask-independent stem fold, computed
            # once per context by the evaluator (SplitEval.pre) — presence
            # is a trace-time (pytree structure) decision, never a retrace
            logits = self.forward(ctx["params"], masks, batch["images"],
                                  pre=ctx.get("pre"))
            return jnp.mean((jnp.argmax(logits, -1) == batch["labels"])
                            .astype(jnp.float32)) * 100.0
        return eval_fn

    def make_suffix_eval_fns(self):
        """Split-forward closure bundle for ``engine.SuffixEvaluator``.

        ``prefix(site, masks, ctx) -> cached`` runs the shared part of the
        net once per (site, step); ``suffix(site, masks, cached, ctx) ->
        acc[%]`` is what the engine vmaps over the candidate axis —
        per-candidate work shrinks to the layers at/after the mutated site.
        ``ctx = {"params", "batch"}`` rides as evaluator context exactly
        like :meth:`make_joint_eval_fn` (batch-shardable on a
        ``("cand", "batch")`` mesh, so the cached prefix never gathers).
        """
        from repro.core import engine

        def prefix_fn(site, masks, ctx):
            return self.forward_prefix(ctx["params"], masks,
                                       ctx["batch"]["images"], site)

        def prefix_ext_fn(from_site, site, masks, cached, ctx):
            return self.forward_prefix(ctx["params"], masks,
                                       ctx["batch"]["images"], site,
                                       from_site=from_site, cached=cached)

        def suffix_fn(site, masks, cached, ctx):
            logits = self.forward_suffix(ctx["params"], masks, cached, site)
            return jnp.mean((jnp.argmax(logits, -1) == ctx["batch"]["labels"])
                            .astype(jnp.float32)) * 100.0

        def pre_fn(ctx):
            return self.forward_pre(ctx["params"], ctx["batch"]["images"])

        return engine.SplitEval(
            prefix=prefix_fn, suffix=suffix_fn,
            full=self.make_joint_eval_fn(),
            site_order=self.site_order(),
            site_segment=self.site_segments(),
            suffix_sites=self.suffix_sites,
            prefix_fraction=self.site_prefix_fractions(),
            prefix_ext=prefix_ext_fn,
            pre=pre_fn)

    def make_eval_acc(self, params, batch):
        """Host callable ``mask_tree -> float`` (jitted single-candidate
        path) — what ``run_bcd``'s eval_acc argument expects."""
        fn = jax.jit(self.make_eval_fn(params, batch))
        return lambda masks: float(fn(M.as_device(masks)))
