"""Unified LM backbone for all assigned architectures.

A model is head_blocks (unrolled) + a lax.scan over ``n_repeats`` copies of
``cfg.pattern`` (stacked params ⇒ compact HLO, O(1) compile cost in depth) +
a tail (pattern remainder, unrolled).  Block kinds: dense (attn+FFN), moe
(attn+MoE), mamba (Mamba2), rwkv (RWKV-6 time+channel mix), attn_only
(zamba2's shared attention block — params shared across repeats, caches not).

Masks (core.linearize) attach to every block's elementwise nonlinearity and
ride through the scan as stacked xs, so BCD candidate evaluation re-runs the
same compiled forward with different mask values — no recompilation.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, Block
from repro.core import linearize
from . import layers, moe as moe_lib, ssm

# --------------------------------------------------------------- sub-configs


def _attn_cfg(cfg: ArchConfig, blk: Block) -> layers.AttnCfg:
    return layers.AttnCfg(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, qk_norm=cfg.qk_norm, window=blk.window,
        rope_theta=blk.rope_theta)


def _moe_cfg(cfg: ArchConfig) -> moe_lib.MoECfg:
    return moe_lib.MoECfg(
        d_model=cfg.d_model, n_experts=cfg.n_experts, top_k=cfg.top_k,
        d_ff_expert=cfg.d_ff_expert,
        n_shared=1 if cfg.n_shared_experts else 0,
        d_ff_shared=cfg.d_ff_shared, capacity_factor=cfg.capacity_factor,
        dispatch=cfg.moe_dispatch)


def _mamba_cfg(cfg: ArchConfig) -> ssm.MambaCfg:
    di = cfg.d_inner
    return ssm.MambaCfg(d_model=cfg.d_model, d_inner=di,
                        n_heads=di // cfg.mamba_head_dim,
                        head_dim=cfg.mamba_head_dim, d_state=cfg.ssm_state)


def _rwkv_cfg(cfg: ArchConfig) -> ssm.RWKVCfg:
    return ssm.RWKVCfg(d_model=cfg.d_model, d_ff=cfg.d_ff,
                       head_dim=cfg.rwkv_head_dim)


def _sub(tree: Dict, prefix: str) -> Dict:
    """Sub-tree of a ``"<prefix>.<suffix>"``-keyed dict, keys stripped."""
    return {k.split(".", 1)[1]: v for k, v in tree.items()
            if k.startswith(prefix + ".")}


def _fence(x):
    """Segment-boundary compilation fence (``lax.optimization_barrier``).

    Every split-forward cut point is a hard program boundary in the
    prefix/suffix jits, so the segment after it compiles in isolation
    there.  In the unsegmented forward the same boundary is an internal
    value that XLA freely fuses across (embed fold into the first head
    block, an unrolled trip-1 scan body into the final norm, …), which can
    change the compiled arithmetic by an ulp or two and break the bitwise
    ``prefix∘suffix == forward`` contract.  Fencing every segment boundary
    in EVERY path makes each segment compile in isolation everywhere, so
    the contract holds by construction.  The fence only blocks fusion
    across the (B, S, D) residual stream — which the residual adds
    materialize anyway — so it is free in practice."""
    return jax.lax.optimization_barrier(x)


def _positions(B: int, S: int, cache_len):
    """(B, S) absolute positions.  ``cache_len`` scalar: every row starts at
    the same offset (the one-shot serve path).  ``cache_len`` (B,): per-row
    offsets — continuous-batching decode, where each slot sits at its own
    sequence position."""
    cl = jnp.asarray(cache_len)
    if cl.ndim == 1:
        return jnp.arange(S)[None, :] + cl[:, None]
    return jnp.broadcast_to((jnp.arange(S) + cl)[None, :], (B, S))


def _sites_for(cfg: ArchConfig, blk: Block) -> Dict[str, linearize.MaskSite]:
    rep = cfg.act_when_masked
    if blk.kind == "dense":
        return {"ffn": linearize.MaskSite((cfg.d_ff,), cfg.act, rep)}
    if blk.kind == "moe":
        out = {"moe": linearize.MaskSite(
            (cfg.n_experts, cfg.d_ff_expert), cfg.act, rep)}
        if cfg.n_shared_experts:
            out["moe_shared"] = linearize.MaskSite(
                (cfg.d_ff_shared,), cfg.act, rep)
        return out
    if blk.kind == "mamba":
        return {"mamba": linearize.MaskSite((cfg.d_inner,), "silu", rep)}
    if blk.kind == "rwkv":
        return {"rwkv": linearize.MaskSite((cfg.d_ff,), "sqrelu", rep)}
    return {}


class LM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        # Set by the step factories (train/serve): PartitionSpec for the
        # (B, S, D) activation stream.  GSPMD's fixpoint propagation drops the
        # batch sharding across while-loop (scan) carries, so we re-assert it
        # at the embed output and at every scan-body entry.
        self.activation_spec: Optional[P] = None

    def _constrain(self, x):
        if self.activation_spec is not None:
            return jax.lax.with_sharding_constraint(x, self.activation_spec)
        return x

    # ------------------------------------------------------------ init

    def _layer_init(self, key, blk: Block):
        cfg, dt = self.cfg, self.dtype
        ks = jax.random.split(key, 2)
        d = cfg.d_model
        if blk.kind in ("dense", "moe", "attn_only"):
            p = {"ln1": layers.rmsnorm_init(d),
                 "attn": layers.attn_init(ks[0], _attn_cfg(cfg, blk), dt)}
            if blk.kind == "dense":
                p["ln2"] = layers.rmsnorm_init(d)
                p["ffn"] = layers.ffn_init(ks[1], d, cfg.d_ff,
                                           gated=cfg.gated_ffn, dtype=dt)
            elif blk.kind == "moe":
                p["ln2"] = layers.rmsnorm_init(d)
                p["moe"] = moe_lib.moe_init(ks[1], _moe_cfg(cfg), dt)
            return p
        if blk.kind == "mamba":
            return {"ln": layers.rmsnorm_init(d),
                    "mamba": ssm.mamba_init(ks[0], _mamba_cfg(cfg), dt)}
        if blk.kind == "rwkv":
            return {"ln1": layers.rmsnorm_init(d),
                    "ln2": layers.rmsnorm_init(d),
                    "tmix": ssm.rwkv_init(ks[0], _rwkv_cfg(cfg), dt)}
        raise ValueError(blk.kind)

    def init(self, key):
        cfg, dt = self.cfg, self.dtype
        ke, kh, kst, kt = jax.random.split(key, 4)
        params = {
            "embed": (jax.random.normal(ke, (cfg.vocab, cfg.d_model))
                      * cfg.d_model ** -0.5).astype(dt),
            "final_norm": layers.rmsnorm_init(cfg.d_model),
            "head": [self._layer_init(jax.random.fold_in(kh, i), blk)
                     for i, blk in enumerate(cfg.head_blocks)],
            "tail": [self._layer_init(jax.random.fold_in(kt, i), blk)
                     for i, blk in enumerate(cfg.tail)],
        }
        stack = {}
        R = cfg.n_repeats
        for pos, blk in enumerate(cfg.pattern):
            kp = jax.random.fold_in(kst, pos)
            if blk.shared:
                stack[str(pos)] = self._layer_init(kp, blk)
            else:
                stack[str(pos)] = jax.vmap(
                    lambda k, blk=blk: self._layer_init(k, blk)
                )(jax.random.split(kp, R))
        params["stack"] = stack
        return params

    # ------------------------------------------------------------ masks

    def mask_sites(self) -> Dict[str, linearize.MaskSite]:
        cfg = self.cfg
        out = {}
        for i, blk in enumerate(cfg.head_blocks):
            for suf, site in _sites_for(cfg, blk).items():
                out[f"h{i}.{suf}"] = site
        for pos, blk in enumerate(cfg.pattern):
            for suf, site in _sites_for(cfg, blk).items():
                out[f"s{pos}.{suf}"] = dataclasses.replace(
                    site, shape=(cfg.n_repeats,) + site.shape)
        for i, blk in enumerate(cfg.tail):
            for suf, site in _sites_for(cfg, blk).items():
                out[f"t{i}.{suf}"] = site
        return out

    # unstacked site (per-layer) for use inside the scan body
    def _site(self, blk: Block, suf: str) -> linearize.MaskSite:
        return _sites_for(self.cfg, blk)[suf]

    # ------------------------------------------------------------ blocks

    def _layer_apply(self, blk: Block, p, x, msk, ply, soft, positions,
                     cache, cache_len):
        """One block.  msk/ply: dicts suffix->array (unstacked).  cache: dict
        or None.  Returns (x, new_cache)."""
        cfg = self.cfg
        newc = {} if cache is not None else None
        if blk.kind in ("dense", "moe", "attn_only"):
            h = layers.rmsnorm(p["ln1"], x)
            kv = None if cache is None else cache["kv"]
            a, kv2 = layers.attention(p["attn"], _attn_cfg(cfg, blk), h,
                                      positions, kv_cache=kv,
                                      cache_len=cache_len)
            x = x + a
            if cache is not None:
                newc["kv"] = kv2
            if blk.kind == "dense":
                h = layers.rmsnorm(p["ln2"], x)
                x = x + layers.ffn(p["ffn"], h, msk["ffn"],
                                   self._site(blk, "ffn"),
                                   poly=ply.get("ffn"), soft=soft)
            elif blk.kind == "moe":
                h = layers.rmsnorm(p["ln2"], x)
                mc = _moe_cfg(cfg)
                x = x + moe_lib.moe_ffn(
                    p["moe"], mc, h, msk["moe"], self._site(blk, "moe"),
                    shared_mask=msk.get("moe_shared"),
                    shared_site=(self._site(blk, "moe_shared")
                                 if cfg.n_shared_experts else None),
                    poly=ply.get("moe"), shared_poly=ply.get("moe_shared"),
                    soft=soft, act_spec=self.activation_spec)
            return x, newc
        if blk.kind == "mamba":
            h = layers.rmsnorm(p["ln"], x)
            c = None if cache is None else (cache["ssm"], cache["conv"])
            y, c2 = ssm.mamba_block(p["mamba"], _mamba_cfg(cfg), h,
                                    msk["mamba"], self._site(blk, "mamba"),
                                    poly=ply.get("mamba"), soft=soft, cache=c)
            if cache is not None:
                newc["ssm"], newc["conv"] = c2
            return x + y, newc
        if blk.kind == "rwkv":
            rc = _rwkv_cfg(cfg)
            h = layers.rmsnorm(p["ln1"], x)
            c = None if cache is None else (cache["state"], cache["ptm"])
            y, c2 = ssm.rwkv_time_mix(p["tmix"], rc, h, cache=c)
            x = x + y
            if cache is not None:
                newc["state"], newc["ptm"] = c2
            h = layers.rmsnorm(p["ln2"], x)
            c = None if cache is None else cache["pcm"]
            y, c2 = ssm.rwkv_channel_mix(p["tmix"], rc, h, msk["rwkv"],
                                         self._site(blk, "rwkv"),
                                         poly=ply.get("rwkv"), soft=soft,
                                         cache=c)
            if cache is not None:
                newc["pcm"] = c2
            return x + y, newc
        raise ValueError(blk.kind)

    # ------------------------------------------------------------ forward

    def _run_stack(self, params, masks, x, positions, *, poly, soft,
                   cache=None, cache_len=0, remat=False, lo_repeat=0,
                   hi_repeat=None):
        """The scanned repeat stack: returns (x, scanned_cache).

        Shared verbatim by :meth:`forward` and the split forwards
        (:meth:`forward_prefix` / :meth:`forward_suffix`), so both trace
        the identical scan — the bitwise split-forward contract depends on
        it.

        ``lo_repeat``/``hi_repeat`` run only scan repeats ``[lo, hi)`` —
        the split forwards' per-repeat carry checkpoints.  The slice of the
        stacked xs is static (Python ints), so the scan body traces
        identically to the full run, and the handoff at a repeat boundary
        is bitwise: ``lax.scan`` materializes the carry between iterations
        either way, so running repeats ``[0, r)`` then ``[r, R)`` from the
        returned carry replays the exact per-iteration math of ``[0, R)``.
        In the eval path (``cache=None``) the carry IS the (B, S, D) hidden
        state — the repeat-r checkpoint is an ordinary boundary activation.
        """
        cfg = self.cfg
        pattern = cfg.pattern
        R = cfg.n_repeats
        hi_repeat = R if hi_repeat is None else hi_repeat
        xs = {"params": {str(p): params["stack"][str(p)]
                         for p, blk in enumerate(pattern) if not blk.shared},
              "masks": {f"s{p}.{suf}": masks[f"s{p}.{suf}"]
                        for p, blk in enumerate(pattern)
                        for suf in _sites_for(cfg, blk)},
              # stacked poly arrive as (3, R, ·) — scan slices dim 0, so
              # move R first: (R, 3, ·)
              "poly": {k: jnp.moveaxis(v, 1, 0)
                       for k, v in poly.items() if k.startswith("s")}}
        if cache is not None:
            xs["cache"] = cache["stack"]
        if lo_repeat > 0 or hi_repeat < R:
            xs = jax.tree.map(lambda a: a[lo_repeat:hi_repeat], xs)
        # XLA unrolls trip-count-1 while loops and then fuses the inlined
        # body with surrounding ops (embed fold, final norm), changing the
        # arithmetic vs a multi-trip loop whose body compiles in isolation.
        # Fencing the carry forces a sliced (possibly single-repeat) scan
        # to compile in isolation too, keeping mid-scan prefix∘suffix
        # bitwise-equal to the unsegmented forward.  For a multi-trip scan
        # the loop boundary already isolates the body, so the fence is a
        # no-op there.
        x = _fence(x)
        R = hi_repeat - lo_repeat

        def body(x, sl):
            x = self._constrain(x)
            newcs = {}
            for p, blk in enumerate(pattern):
                lp = (params["stack"][str(p)] if blk.shared
                      else sl["params"][str(p)])
                msk = _sub(sl["masks"], f"s{p}")
                pl = _sub(sl["poly"], f"s{p}")
                c = sl["cache"][str(p)] if cache is not None else None
                x, nc = self._layer_apply(blk, lp, x, msk, pl, soft,
                                          positions, c, cache_len)
                newcs[str(p)] = nc
            return x, (newcs if cache is not None else None)

        G = self.cfg.remat_group
        if remat and cache is None and G > 1 and R % G == 0:
            # Hierarchical remat: outer scan over R/G groups saves only
            # group-boundary activations (G× less stacked-carry memory);
            # the group forward is recomputed (with per-layer inner remat)
            # during backward.  See EXPERIMENTS.md §Perf.
            xsG = jax.tree.map(
                lambda a: a.reshape((R // G, G) + a.shape[1:]), xs)
            inner = jax.checkpoint(body)

            def group_body(x, slG):
                for g in range(G):
                    x, _ = inner(x, jax.tree.map(lambda a: a[g], slG))
                return x, None

            out, scanned = jax.lax.scan(jax.checkpoint(group_body), x, xsG)
            return _fence(out), scanned
        body_fn = jax.checkpoint(body) if remat else body
        out, scanned = jax.lax.scan(body_fn, x, xs)
        return _fence(out), scanned

    def forward(self, params, masks, tokens, *, prefix_embeds=None,
                poly=None, soft=False, cache=None, cache_len=0, remat=False,
                return_hidden=False, pre=None):
        """Returns (logits (B,S,V), new_cache); with return_hidden=True the
        first element is the final-norm hidden state (B,S,D) instead (the
        caller owns the head matmul — e.g. chunked CE, §Perf).

        ``pre``: a cached :meth:`forward_pre` result (the mask-independent
        embed fold) — the fold resumes after segment 0 and ``tokens`` is
        only consumed for its length.  Eval-path only (mutually exclusive
        with ``prefix_embeds``)."""
        cfg = self.cfg
        poly = poly or {}
        if pre is not None:
            x = pre
        else:
            x = jnp.take(params["embed"], tokens, axis=0)
            if prefix_embeds is not None:
                x = jnp.concatenate([prefix_embeds.astype(x.dtype), x],
                                    axis=1)
            x = self._constrain(x)
        B, S, _ = x.shape
        positions = _positions(B, S, cache_len)

        new_cache = {"head": [], "stack": {}, "tail": []} \
            if cache is not None else None

        for i, blk in enumerate(cfg.head_blocks):
            c = None if cache is None else cache["head"][i]
            x, nc = self._layer_apply(blk, params["head"][i], _fence(x),
                                      _sub(masks, f"h{i}"),
                                      _sub(poly, f"h{i}"), soft,
                                      positions, c, cache_len)
            if cache is not None:
                new_cache["head"].append(nc)

        x, scanned_cache = self._run_stack(
            params, masks, x, positions, poly=poly, soft=soft, cache=cache,
            cache_len=cache_len, remat=remat)
        if cache is not None:
            new_cache["stack"] = scanned_cache

        for i, blk in enumerate(cfg.tail):
            c = None if cache is None else cache["tail"][i]
            x, nc = self._layer_apply(blk, params["tail"][i], _fence(x),
                                      _sub(masks, f"t{i}"),
                                      _sub(poly, f"t{i}"), soft,
                                      positions, c, cache_len)
            if cache is not None:
                new_cache["tail"].append(nc)

        x = layers.rmsnorm(params["final_norm"], x)
        if return_hidden:
            return x, new_cache
        logits = x @ params["embed"].T.astype(x.dtype)
        return logits, new_cache

    # ------------------------------------------------------- split forward
    #
    # Segment boundaries for prefix-reuse candidate evaluation
    # (core.engine.SuffixEvaluator): embed | head block i … | stack repeat 0
    # … stack repeat R-1 | tail block i … | final norm + logits.  The
    # scanned stack contributes one segment PER REPEAT: the eval-path scan
    # carry is exactly the (B, S, D) hidden state, so the repeat-r boundary
    # is a carry checkpoint — forward_prefix stops the scan after repeat
    # r-1 and forward_suffix resumes it from the cached carry instead of
    # re-running the whole stack.  Stack sites are addressed two ways: the
    # REAL mask name ("s0.ffn" — the key in the mask tree, whose (R, ·)
    # array spans every repeat) maps to its repeat-0 segment (the
    # shallowest cut its coordinates can force), while virtual
    # repeat-qualified names ("s0.ffn@r") address the per-repeat segments.
    # site_order lists the virtual names; grouping resolves each candidate
    # coordinate's true repeat row arithmetically
    # (masks.group_blocks_by_site repeat_sites=).  The split forwards reuse
    # _layer_apply and _run_stack verbatim, so suffix(prefix(x)) traces the
    # same primitives as forward(x) (eval path: no cache / remat /
    # prefix_embeds).

    def _segment_of_site(self) -> Dict[str, int]:
        cfg = self.cfg
        H = len(cfg.head_blocks)
        R = cfg.n_repeats
        out = {}
        for i, blk in enumerate(cfg.head_blocks):
            for suf in _sites_for(cfg, blk):
                out[f"h{i}.{suf}"] = 1 + i
        for pos, blk in enumerate(cfg.pattern):
            for suf in _sites_for(cfg, blk):
                out[f"s{pos}.{suf}"] = 1 + H
                for r in range(R):
                    out[f"s{pos}.{suf}@{r}"] = 1 + H + r
        for i, blk in enumerate(cfg.tail):
            for suf in _sites_for(cfg, blk):
                out[f"t{i}.{suf}"] = 1 + H + R + i
        return out

    def site_repeats(self) -> Dict[str, int]:
        """Real stack mask name -> scan repeat count its (R, ·) array spans.

        The repeat-aware grouping contract (``masks.group_blocks_by_site``
        ``repeat_sites=``): a stack site's per-repeat segments are
        consecutive from its base (repeat-0) segment, and its flat mask
        coordinates are laid out repeat-major, so a coordinate's segment is
        ``base + local_offset // (size // R)``."""
        cfg = self.cfg
        return {f"s{pos}.{suf}": cfg.n_repeats
                for pos, blk in enumerate(cfg.pattern)
                for suf in _sites_for(cfg, blk)}

    def site_order(self) -> Tuple[str, ...]:
        """All mask sites in forward (topological) order.

        Stack sites appear once per scan repeat under their virtual
        repeat-qualified name (``"s0.ffn@1"``); head/tail sites under their
        real name.  Real stack names are deliberately absent — each segment
        gets exactly one representative, and the engine's per-segment jits
        key off the names listed here."""
        seg = self._segment_of_site()
        reps = self.site_repeats()
        return tuple(sorted((s for s in seg if s not in reps),
                            key=lambda s: (seg[s], s)))

    def site_segments(self) -> Dict[str, int]:
        """site -> segment index (sites sharing a segment share a prefix).

        Contains BOTH namings of stack sites: real mask names at their
        repeat-0 segment (mask-tree diffing, grouping rank lookups) and
        virtual ``@r`` names at repeat r's segment (prefix/suffix cuts)."""
        return self._segment_of_site()

    def suffix_sites(self, site: str) -> Tuple[str, ...]:
        """Real mask names consumed by :meth:`forward_suffix` for this cut.

        These are the keys the engine slices candidate stacked trees by, so
        only real (mask-tree) names appear.  A real site is included iff
        its DEEPEST segment is at/after the cut — a stack site's (R, ·)
        array reaches repeat R-1, so a cut at any repeat ships the full
        stack arrays (rows before the cut repeat ride along but are never
        read: the suffix statically slices the scan xs)."""
        seg = self._segment_of_site()
        cut = seg[site]
        reps = self.site_repeats()

        def deepest(s):
            return seg[s] + (reps[s] - 1 if s in reps else 0)
        return tuple(s for s in sorted((k for k in seg if "@" not in k),
                                       key=lambda s: (seg[s], s))
                     if deepest(s) >= cut)

    def forward_prefix(self, params, masks, tokens, site, *, poly=None,
                       soft=False, from_site=None, cached=None):
        """Forward up to (excluding) the segment applying ``site``; returns
        the cached (B, S, D) boundary hidden state.

        A stack cut at repeat r (virtual site ``"s0.ffn@r"``) stops the
        scan after repeat r-1; the returned hidden state is the scan carry
        at that boundary (the eval-path carry IS the (B, S, D) activation),
        so the trie stores and extends carry checkpoints like any other
        prefix — including repeat-to-repeat extension.

        Multi-depth entry: ``from_site``/``cached`` resume from an earlier
        prefix's boundary state instead of the token embedding, folding
        only segments in ``[seg(from_site), seg(site))`` — the prefix-trie
        extension contract (``prefix_ext(a, b, m, prefix(a)) ==
        prefix(b)``, same fold over the same segment list)."""
        cfg = self.cfg
        poly = poly or {}
        seg = self._segment_of_site()
        cut = seg[site]
        lo = 0 if from_site is None else seg[from_site]
        H = len(cfg.head_blocks)
        R = cfg.n_repeats
        if from_site is None:
            x = jnp.take(params["embed"], tokens, axis=0)
            x = self._constrain(x)
        else:
            x = cached
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        for i, blk in enumerate(cfg.head_blocks):
            if 1 + i >= cut:
                break
            if 1 + i < lo:
                continue
            x, _ = self._layer_apply(blk, params["head"][i], _fence(x),
                                     _sub(masks, f"h{i}"),
                                     _sub(poly, f"h{i}"), soft,
                                     positions, None, 0)
        # repeats whose segment 1+H+r lies in [lo, cut)
        lo_r = min(max(lo - (1 + H), 0), R)
        hi_r = min(max(cut - (1 + H), 0), R)
        if hi_r > lo_r:
            x, _ = self._run_stack(params, masks, x, positions, poly=poly,
                                   soft=soft, lo_repeat=lo_r, hi_repeat=hi_r)
        for i, blk in enumerate(cfg.tail):
            if 1 + H + R + i >= cut:
                break
            if 1 + H + R + i < lo:
                continue
            x, _ = self._layer_apply(blk, params["tail"][i], _fence(x),
                                     _sub(masks, f"t{i}"),
                                     _sub(poly, f"t{i}"), soft,
                                     positions, None, 0)
        return x

    def forward_pre(self, params, tokens):
        """Mask-independent head of the network: the segment-0 embed fold
        (token embedding + constraint).  Computed once per evaluator
        context and fed back through ``forward(..., pre=...)`` — the
        "depth-0 prefix" every candidate shares."""
        return self._constrain(jnp.take(params["embed"], tokens, axis=0))

    def forward_suffix(self, params, masks, cached, site, *, poly=None,
                       soft=False):
        """Finish forward from a :meth:`forward_prefix` cache -> logits.

        For a stack cut at repeat r the scan RESUMES from the cached carry
        (repeats ``[r, R)`` only) — a mid-scan candidate no longer re-runs
        the whole stack."""
        cfg = self.cfg
        poly = poly or {}
        cut = self._segment_of_site()[site]
        H = len(cfg.head_blocks)
        R = cfg.n_repeats
        x = cached
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        for i, blk in enumerate(cfg.head_blocks):
            if 1 + i < cut:
                continue
            x, _ = self._layer_apply(blk, params["head"][i], _fence(x),
                                     _sub(masks, f"h{i}"),
                                     _sub(poly, f"h{i}"), soft,
                                     positions, None, 0)
        lo_r = min(max(cut - (1 + H), 0), R)
        if lo_r < R:
            x, _ = self._run_stack(params, masks, x, positions, poly=poly,
                                   soft=soft, lo_repeat=lo_r, hi_repeat=R)
        for i, blk in enumerate(cfg.tail):
            if 1 + H + R + i < cut:
                continue
            x, _ = self._layer_apply(blk, params["tail"][i], _fence(x),
                                     _sub(masks, f"t{i}"),
                                     _sub(poly, f"t{i}"), soft,
                                     positions, None, 0)
        x = layers.rmsnorm(params["final_norm"], x)
        return x @ params["embed"].T.astype(x.dtype)

    def site_prefix_fractions(self, *, seq_len: int = 64) -> Dict[str, float]:
        """site -> fraction of forward FLOPs strictly before its segment.

        Analytic (roofline.lm_segment_fwd_flops, prefill mode, per-sample);
        the suffix cost model thresholds on it.  ``seq_len`` only matters
        through the attention quadratic term.  Keyed by BOTH namings of
        stack sites: the real mask name carries its repeat-0 (shallowest)
        fraction, virtual ``@r`` names the per-repeat fractions."""
        from repro.analysis import roofline
        # per-segment flops: embed(≈0) | head… | stack repeat 0 … R-1 |
        # tail… | logits (one entry PER scan repeat, MoE at true padded
        # slot capacity)
        seg_flops = roofline.lm_segment_fwd_flops(self.cfg, seq_len=seq_len)
        total = max(sum(seg_flops), 1.0)
        before, cum = [], 0.0
        for v in seg_flops:
            before.append(cum / total)
            cum += v
        return {s: before[i] for s, i in self._segment_of_site().items()}

    def make_suffix_eval_fns(self):
        """Split-forward closure bundle for ``engine.SuffixEvaluator`` —
        same contract as ``CNN.make_suffix_eval_fns`` (ctx = {"params",
        "batch"}; the metric is next-token accuracy [%])."""
        from repro.core import engine

        def prefix_fn(site, masks, ctx):
            return self.forward_prefix(ctx["params"], masks,
                                       ctx["batch"]["tokens"][:, :-1], site)

        def prefix_ext_fn(from_site, site, masks, cached, ctx):
            return self.forward_prefix(ctx["params"], masks,
                                       ctx["batch"]["tokens"][:, :-1], site,
                                       from_site=from_site, cached=cached)

        def suffix_fn(site, masks, cached, ctx):
            logits = self.forward_suffix(ctx["params"], masks, cached, site)
            pred = jnp.argmax(logits, -1)
            return jnp.mean((pred == ctx["batch"]["tokens"][:, 1:])
                            .astype(jnp.float32)) * 100.0

        def pre_fn(ctx):
            return self.forward_pre(ctx["params"],
                                    ctx["batch"]["tokens"][:, :-1])

        return engine.SplitEval(
            prefix=prefix_fn, suffix=suffix_fn,
            full=self.make_joint_eval_fn(),
            site_order=self.site_order(),
            site_segment=self.site_segments(),
            suffix_sites=self.suffix_sites,
            prefix_fraction=self.site_prefix_fractions(),
            prefix_ext=prefix_ext_fn,
            pre=pre_fn,
            site_repeats=self.site_repeats())

    # ------------------------------------------------------- eval closures
    #
    # Same contract as models.resnet.CNN: a traceable single-mask-tree
    # closure for the batched/sharded BCD candidate engines (vmapped over
    # the candidate axis) and a host-callable wrapper for sequential use.
    # The metric is next-token accuracy [%] on a fixed token batch — masks
    # ride through the scanned stack as jit inputs, so candidate evaluation
    # never recompiles.

    def make_param_eval_fn(self, batch):
        """Traceable ``(mask_tree, params) -> accuracy[%]`` — params as an
        evaluator context (jit input), for finetuning-between-steps runs."""
        tokens = jnp.asarray(batch["tokens"])

        def eval_fn(masks, params):
            logits, _ = self.forward(params, masks, tokens[:, :-1])
            pred = jnp.argmax(logits, -1)
            return jnp.mean((pred == tokens[:, 1:])
                            .astype(jnp.float32)) * 100.0
        return eval_fn

    def make_eval_fn(self, params, batch):
        fn = self.make_param_eval_fn(batch)
        return lambda masks: fn(masks, params)

    def make_joint_eval_fn(self):
        """Traceable ``(mask_tree, ctx) -> accuracy[%]`` with
        ``ctx = {"params": ..., "batch": ...}`` — same contract as
        ``CNN.make_joint_eval_fn``: the token batch is evaluator context, so
        on a ``("cand", "batch")`` mesh the eval batch shards over
        ``"batch"`` while candidates shard over ``"cand"`` (joint layout for
        trial chunks smaller than the device count)."""
        def eval_fn(masks, ctx):
            tokens = ctx["batch"]["tokens"]
            # "pre" (optional): the mask-independent embed fold, computed
            # once per context by the evaluator (SplitEval.pre)
            logits, _ = self.forward(ctx["params"], masks, tokens[:, :-1],
                                     pre=ctx.get("pre"))
            pred = jnp.argmax(logits, -1)
            return jnp.mean((pred == tokens[:, 1:])
                            .astype(jnp.float32)) * 100.0
        return eval_fn

    def make_eval_acc(self, params, batch):
        from repro.core import masks as M
        fn = jax.jit(self.make_eval_fn(params, batch))
        return lambda masks: float(fn(M.as_device(masks)))

    # ------------------------------------------------------------ cache

    def _layer_cache(self, blk: Block, B: int, max_len: int):
        cfg, dt = self.cfg, self.dtype
        if blk.kind in ("dense", "moe", "attn_only"):
            kv_shape = (B, max_len, cfg.n_kv_heads, cfg.head_dim)
            return {"kv": (jnp.zeros(kv_shape, dt), jnp.zeros(kv_shape, dt))}
        if blk.kind == "mamba":
            mc = _mamba_cfg(cfg)
            return {"ssm": jnp.zeros((B, mc.n_heads, mc.d_state, mc.head_dim),
                                     jnp.float32),
                    "conv": jnp.zeros((B, mc.d_conv - 1, mc.d_inner), dt)}
        if blk.kind == "rwkv":
            rc = _rwkv_cfg(cfg)
            return {"state": jnp.zeros((B, rc.n_heads, rc.head_dim,
                                        rc.head_dim), jnp.float32),
                    "ptm": jnp.zeros((B, cfg.d_model), dt),
                    "pcm": jnp.zeros((B, cfg.d_model), dt)}
        raise ValueError(blk.kind)

    def init_cache(self, B: int, max_len: int):
        cfg = self.cfg
        R = cfg.n_repeats
        stack = {}
        for pos, blk in enumerate(cfg.pattern):
            one = self._layer_cache(blk, B, max_len)
            stack[str(pos)] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (R,) + a.shape), one)
        return {"head": [self._layer_cache(b, B, max_len)
                         for b in cfg.head_blocks],
                "stack": stack,
                "tail": [self._layer_cache(b, B, max_len)
                         for b in cfg.tail]}


# =================================================================== specs

_COL = {"wq", "wk", "wv", "w_gate", "w_up", "w_ck", "w_cr", "w_r", "w_k",
        "w_v", "w_g", "w_w", "w_z", "w_x"}    # (..., in, out): TP on out
_ROW = {"wo", "w_down", "w_out", "w_o", "w_cv"}  # (..., in, out): TP on in
_FSDP_ONLY = {"router", "w_bcdt"}


def _leaf_spec(name: str, shape, data: int, model: int,
               fsdp: bool = True) -> P:
    nd = len(shape)

    def ok(dim_idx, axis_size):
        return shape[dim_idx] % axis_size == 0

    if name == "embed":
        return P("model" if ok(0, model) else None, None)
    if name in _COL:
        sp = ["data" if fsdp and ok(nd - 2, data) else None,
              "model" if ok(nd - 1, model) else None]
    elif name in _ROW:
        sp = ["model" if ok(nd - 2, model) else None,
              "data" if fsdp and ok(nd - 1, data) else None]
    elif name in _FSDP_ONLY:
        sp = ["data" if fsdp and ok(nd - 2, data) else None, None]
    elif name == "conv":
        sp = [None, "model" if ok(nd - 1, model) else None]
    else:
        return P()
    return P(*([None] * (nd - 2) + sp))


def param_specs(params_shape, data: int, model: int, fsdp: bool = True):
    """PartitionSpec tree mirroring the params tree (rule-based on leaf name).

    data/model: mesh axis sizes (for divisibility checks).  fsdp=False turns
    off the ZeRO-3 'data'-axis weight sharding (pure TP baseline).
    """
    def f(path, leaf):
        name = None
        for p in reversed(path):
            if hasattr(p, "key"):
                name = p.key
                break
        return _leaf_spec(name, leaf.shape, data, model, fsdp)
    return jax.tree_util.tree_map_with_path(f, params_shape)


def cache_specs(cache_shape, dp_axes: Tuple[str, ...], B: int, data: int,
                model: int, shard_seq: bool = False):
    """Sharding for decode caches.  KV caches: batch over dp axes (or the
    sequence axis over 'data' when B == 1, long_500k), heads/state over
    'model' when divisible."""
    def f(path, leaf):
        nd = len(leaf.shape)
        if nd >= 3:  # kv (B,S,KV,hd) | ssm (B,nh,N,hd) | state (B,H,hd,hd)
            batch_ok = B % (data) == 0 and B >= data
            sp = [dp_axes if batch_ok and leaf.shape[0] % data == 0 else None]
            if nd == 4 and leaf.shape[1] > 4096:      # kv cache: (B,S,KV,hd)
                sp.append("data" if (shard_seq and not batch_ok and
                                     leaf.shape[1] % data == 0) else None)
                sp.append("model" if leaf.shape[2] % model == 0 else None)
                sp.append(None if leaf.shape[2] % model == 0 else
                          ("model" if leaf.shape[3] % model == 0 else None))
            else:
                sp.append("model" if leaf.shape[1] % model == 0 else None)
                sp += [None] * (nd - 2)
            return P(*sp)
        if nd == 2:   # prev-token (B,d)
            return P(dp_axes if leaf.shape[0] % data == 0 and B >= data
                     else None,
                     "model" if leaf.shape[1] % model == 0 else None)
        return P()
    return jax.tree_util.tree_map_with_path(f, cache_shape)
