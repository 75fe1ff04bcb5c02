"""Prefix-reuse candidate evaluation: split-forward contract + suffix engine.

Three layers of guarantees under test:

1. **Model contract** — ``forward_suffix(p, m, forward_prefix(p, m, x, s), s)
   == forward(p, m, x)`` *bitwise* for every site ``s``, both model
   families.  Prefix/suffix fold the same segment list / reuse the same
   layer helpers as forward, so a composed trace emits identical
   primitives; this suite pins that down.
2. **Selection equivalence** — the suffix backend evaluates in site-major
   order (one cached prefix per group) but replays Alg. 2's sampling-order
   selection rules; ``run_bcd`` must pick bit-identical blocks vs the
   sequential reference at every prefetch depth, with identical trial
   counts and early-exit flags.
3. **Plumbing** — sited chunks never straddle a segment (coalesced
   fallback chunks may — they share no prefix), the cost model falls
   shallow cuts back to the full forward, and every prefix-trie entry is
   batch-sharded (never gathered) on a forced 4-device
   ``("cand", "batch")`` mesh.
"""
import os

import numpy as np
import jax
import pytest

# hypothesis is an optional dev dep (pip extra: test) — bare environments
# must still collect/run the deterministic tests, so only the property
# tests below are guarded.
try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

from repro.configs.base import ArchConfig, Block, get_config
from repro.core import bcd, engine, linearize, masks as M
from repro.core.engine import SuffixCostModel
from repro.data import ImageDatasetCfg, SyntheticImages
from repro.models.lm import LM
from repro.models.resnet import CNN, CNNConfig


# ------------------------------------------------------- model contract


def _assert_split_bitwise(model, params, masks, forward_args, sites):
    md = M.as_device(masks)
    full = np.asarray(jax.jit(model.forward)(params, md, *forward_args))
    for site in sites:
        def composed(p, m, x, site=site):
            return model.forward_suffix(
                p, m, model.forward_prefix(p, m, x, site), site)
        out = jax.jit(composed)(params, md, *forward_args)
        np.testing.assert_array_equal(
            np.asarray(out), full,
            err_msg=f"prefix∘suffix != forward at site {site}")


def test_cnn_split_forward_bitwise_per_site():
    model = CNN(CNNConfig("tiny", 4, 16, ((8, 1, 1), (16, 1, 2)),
                          stem_channels=8))
    params = model.init(jax.random.PRNGKey(0))
    masks = linearize.init_masks(model.mask_sites())
    # zero a few coordinates so masks are non-trivial
    rng = np.random.default_rng(0)
    masks = M.sample_removal_block(rng, masks, 64)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    assert set(model.site_order()) == set(model.mask_sites())
    _assert_split_bitwise(model, params, masks, (x,), model.site_order())


def test_wide_cnn_split_forward_bitwise_per_site():
    model = CNN(CNNConfig("wrn-mini", 4, 16,
                          ((8, 1, 1), (16, 1, 2), (16, 1, 2)),
                          stem_channels=8, wide=True))
    params = model.init(jax.random.PRNGKey(0))
    masks = linearize.init_masks(model.mask_sites())
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    _assert_split_bitwise(model, params, masks, (x,), model.site_order())


def _tiny_lm():
    # 1 head block + scanned (2 patterns x 2 repeats) + 1 tail block: every
    # segment kind (head / stack / tail) gets a cut
    cfg = ArchConfig(
        name="tiny-split", family="dense", n_layers=6, d_model=32,
        n_heads=2, n_kv_heads=2, d_ff=48, vocab=64, head_dim=16,
        pattern=(Block("dense"), Block("dense")),
        head_blocks=(Block("dense"),), dtype="float32")
    assert cfg.n_repeats == 2 and len(cfg.tail) == 1
    return LM(cfg)


def test_lm_split_forward_bitwise_per_site():
    model = _tiny_lm()
    params = model.init(jax.random.PRNGKey(0))
    masks = linearize.init_masks(model.mask_sites())
    rng = np.random.default_rng(0)
    masks = M.sample_removal_block(rng, masks, 16)
    tokens = np.asarray(rng.integers(0, model.cfg.vocab, (2, 17),
                                     dtype=np.int32))
    md = M.as_device(masks)
    full = np.asarray(
        jax.jit(lambda p, m, t: model.forward(p, m, t)[0])(params, md,
                                                           tokens))
    # stack sites are addressed by virtual per-repeat names, one per
    # (site, repeat); repeat-0 cuts sort at the same segment
    assert model.site_order() == ("h0.ffn", "s0.ffn@0", "s1.ffn@0",
                                  "s0.ffn@1", "s1.ffn@1", "t0.ffn")
    assert model.site_repeats() == {"s0.ffn": 2, "s1.ffn": 2}
    for site in model.site_order():
        def composed(p, m, t, site=site):
            return model.forward_suffix(
                p, m, model.forward_prefix(p, m, t, site), site)
        out = np.asarray(jax.jit(composed)(params, md, tokens))
        np.testing.assert_array_equal(out, full, err_msg=site)


# ----------------------------------------- SSM / MoE family contract


def _family_setup(arch_id, seed=0, B=2, S=17):
    """Reduced-config LM + non-trivial masks + a token batch."""
    model = LM(get_config(arch_id).reduced())
    params = model.init(jax.random.PRNGKey(seed))
    masks = linearize.init_masks(model.mask_sites())
    rng = np.random.default_rng(seed)
    masks = M.sample_removal_block(rng, masks, 16)
    tokens = np.asarray(rng.integers(0, model.cfg.vocab, (B, S),
                                     dtype=np.int32))
    return model, params, masks, tokens


def _assert_lm_split_bitwise(model, params, masks, tokens):
    """prefix∘suffix == forward bitwise at every site, with prefix and
    suffix compiled as SEPARATE jits (the engine's program boundaries)."""
    md = M.as_device(masks)
    full = np.asarray(jax.jit(
        lambda p, m, t: model.forward(p, m, t)[0])(params, md, tokens))
    for site in model.site_order():
        pj = jax.jit(lambda p, m, t, s=site: model.forward_prefix(p, m, t, s))
        sj = jax.jit(lambda p, m, c, s=site: model.forward_suffix(p, m, c, s))
        out = np.asarray(sj(params, md, pj(params, md, tokens)))
        np.testing.assert_array_equal(
            out, full, err_msg=f"prefix∘suffix != forward at site {site}")
    return md, full


def test_ssm_split_forward_bitwise_per_site_including_mid_scan():
    """rwkv6 reduced is a pure scanned stack (no head/tail): every cut is a
    carry checkpoint, and the repeat-1 cut resumes the scan mid-stack."""
    model, params, masks, tokens = _family_setup("rwkv6_3b")
    assert model.site_order() == ("s0.rwkv@0", "s0.rwkv@1")
    assert model.site_repeats() == {"s0.rwkv": 2}
    _assert_lm_split_bitwise(model, params, masks, tokens)


def test_moe_split_forward_bitwise_per_site_including_mid_scan():
    """deepseek-moe reduced: dense head + scanned MoE stack with routed +
    shared-expert sites; capacity-overflow token dropping is live at this
    sequence length, so routing determinism is part of the contract."""
    model, params, masks, tokens = _family_setup("deepseek_moe_16b")
    assert model.site_order() == ("h0.ffn", "s0.moe@0", "s0.moe_shared@0",
                                  "s0.moe@1", "s0.moe_shared@1")
    _assert_lm_split_bitwise(model, params, masks, tokens)


@pytest.mark.parametrize("arch_id", ["rwkv6_3b", "deepseek_moe_16b"])
def test_carry_checkpoint_prefix_extension_roundtrip(arch_id):
    """Trie-extension contract along repeats: ``prefix_ext(a, b, m,
    prefix(a)) == prefix(b)`` bitwise for consecutive cuts — the carry
    checkpoint at repeat r resumes the scan instead of re-running it."""
    model, params, masks, tokens = _family_setup(arch_id, seed=1)
    md = M.as_device(masks)
    order, segs = model.site_order(), model.site_segments()
    pairs = [(order[i], order[i + 1]) for i in range(len(order) - 1)
             if segs[order[i]] < segs[order[i + 1]]]
    assert pairs, "no consecutive cut pair to extend across"
    for a, b in pairs:
        pa = jax.jit(lambda m, a=a: model.forward_prefix(
            params, m, tokens, a))(md)
        pe = jax.jit(lambda m, c, a=a, b=b: model.forward_prefix(
            params, m, tokens, b, from_site=a, cached=c))(md, pa)
        pb = jax.jit(lambda m, b=b: model.forward_prefix(
            params, m, tokens, b))(md)
        np.testing.assert_array_equal(
            np.asarray(pe), np.asarray(pb),
            err_msg=f"prefix_ext({a} -> {b}) != prefix({b})")


def test_suffix_trie_extends_along_repeats_and_row_diff_invalidation():
    """Carry-aware prefix caching: a repeat-0 checkpoint is EXTENDED to the
    repeat-1 cut (one more scan repeat, no recompute from tokens), and
    ``begin_step`` diffing is per repeat row — editing only repeat-1 rows
    of the stacked base mask keeps every checkpoint warm, editing repeat-0
    rows drops the mid-scan one."""
    model = LM(get_config("rwkv6_3b").reduced())
    params = model.init(jax.random.PRNGKey(2))
    masks0 = linearize.init_masks(model.mask_sites())
    tokens = np.asarray(np.random.default_rng(2).integers(
        0, model.cfg.vocab, (2, 17), dtype=np.int32))
    ctx = {"params": params, "batch": {"tokens": tokens}}
    ev = engine.SuffixEvaluator(model.make_suffix_eval_fns(), context=ctx,
                                pad_to=4)
    seq = engine.SequentialEvaluator(
        model.make_eval_acc(params, {"tokens": tokens}))
    segs, reps = model.site_segments(), model.site_repeats()
    rng = np.random.default_rng(0)
    idx0 = M.sample_removal_indices_within(rng, masks0, 8, 4, ["s0.rwkv@0"],
                                           repeat_sites=reps)
    idx1 = M.sample_removal_indices_within(rng, masks0, 8, 4, ["s0.rwkv@1"],
                                           repeat_sites=reps)
    st0 = M.materialize_candidates(masks0, idx0)
    st1 = M.materialize_candidates(masks0, idx1)
    ev.begin_step(masks0)
    a0 = ev.evaluate(engine.SitedChunk("s0.rwkv@0", st0))
    np.testing.assert_allclose(a0, seq.evaluate(st0), atol=1e-4)
    a1 = ev.evaluate(engine.SitedChunk("s0.rwkv@1", st1))
    np.testing.assert_allclose(a1, seq.evaluate(st1), atol=1e-4)
    assert ev.trie.extensions == 1 and ev.trie.misses == 1, \
        (ev.trie.extensions, ev.trie.misses)
    assert ev.trie.depths() == (segs["s0.rwkv@0"], segs["s0.rwkv@1"])
    # repeat-1-only base edit: prefixes fold repeats strictly BEFORE their
    # cut, so both carry checkpoints stay warm
    edited = {k: np.array(v) for k, v in masks0.items()}
    edited["s0.rwkv"][1].flat[0] = 0.0
    ev.begin_step(edited)
    assert ev.trie.depths() == (segs["s0.rwkv@0"], segs["s0.rwkv@1"])
    # a repeat-0 edit invalidates the mid-scan checkpoint (it folded that
    # repeat) but keeps the embed-only depth
    edited2 = {k: np.array(v) for k, v in masks0.items()}
    edited2["s0.rwkv"][0].flat[0] = 0.0
    ev.begin_step(edited2)
    assert ev.trie.depths() == (segs["s0.rwkv@0"],)


if HAS_HYPOTHESIS:
    _PROP_LM = {}

    def _prop_lm():
        if not _PROP_LM:
            cfg = ArchConfig(
                name="tiny-repeats", family="dense", n_layers=6, d_model=32,
                n_heads=2, n_kv_heads=2, d_ff=48, vocab=64, head_dim=16,
                pattern=(Block("dense"),), head_blocks=(Block("dense"),),
                dtype="float32")
            model = LM(cfg)
            assert cfg.n_repeats == 5      # 6 layers, one head block
            _PROP_LM["model"] = model
            _PROP_LM["params"] = model.init(jax.random.PRNGKey(0))
        return _PROP_LM["model"], _PROP_LM["params"]

    @settings(deadline=None, max_examples=10)
    @given(r=st.integers(min_value=0, max_value=4),
           seed=st.integers(min_value=0, max_value=2 ** 16))
    def test_property_cut_at_any_repeat_matches_unsegmented(r, seed):
        """Cutting the scanned stack at an arbitrary repeat r is bitwise
        the unsegmented forward, for arbitrary masks and token batches."""
        model, params = _prop_lm()
        rng = np.random.default_rng(seed)
        masks = linearize.init_masks(model.mask_sites())
        masks = M.sample_removal_block(rng, masks, 8)
        tokens = np.asarray(rng.integers(0, model.cfg.vocab, (2, 9),
                                         dtype=np.int32))
        md = M.as_device(masks)
        site = f"s0.ffn@{r}"
        full = np.asarray(jax.jit(
            lambda m, t: model.forward(params, m, t)[0])(md, tokens))
        out = np.asarray(jax.jit(
            lambda m, t, s=site: model.forward_suffix(
                params, m, model.forward_prefix(params, m, t, s), s))(
                    md, tokens))
        np.testing.assert_array_equal(out, full, err_msg=site)
else:
    def test_property_cut_at_any_repeat_matches_unsegmented():
        pytest.skip("hypothesis not installed (pip extra: test)")


def _assert_pre_contract(split, ctx, masks):
    """SplitEval.pre contract: ``full(m, {**ctx, "pre": pre(ctx)})`` is
    bitwise ``full(m, ctx)`` — the depth-0 analogue of prefix∘suffix."""
    md = M.as_device(masks)
    pre = jax.jit(split.pre)(ctx)
    base = np.asarray(jax.jit(split.full)(md, ctx))
    folded = np.asarray(jax.jit(split.full)(md, {**ctx, "pre": pre}))
    np.testing.assert_array_equal(folded, base)


def test_cnn_pre_fold_bitwise():
    model = CNN(CNNConfig("tiny", 4, 16, ((8, 1, 1), (16, 1, 2)),
                          stem_channels=8))
    params = model.init(jax.random.PRNGKey(0))
    masks = linearize.init_masks(model.mask_sites())
    rng = np.random.default_rng(0)
    masks = M.sample_removal_block(rng, masks, 16)
    ctx = {"params": params,
           "batch": {"images": np.asarray(
                         rng.standard_normal((2, 16, 16, 3)), np.float32),
                     "labels": np.asarray(rng.integers(0, 4, (2,)),
                                          np.int32)}}
    _assert_pre_contract(model.make_suffix_eval_fns(), ctx, masks)


def test_wide_cnn_pre_fold_bitwise():
    model = CNN(CNNConfig("wrn-mini", 4, 16,
                          ((8, 1, 1), (16, 1, 2), (16, 1, 2)),
                          stem_channels=8, wide=True))
    params = model.init(jax.random.PRNGKey(0))
    masks = linearize.init_masks(model.mask_sites())
    rng = np.random.default_rng(1)
    ctx = {"params": params,
           "batch": {"images": np.asarray(
                         rng.standard_normal((2, 16, 16, 3)), np.float32),
                     "labels": np.asarray(rng.integers(0, 4, (2,)),
                                          np.int32)}}
    _assert_pre_contract(model.make_suffix_eval_fns(), ctx, masks)


def test_lm_pre_fold_bitwise():
    model = _tiny_lm()
    params = model.init(jax.random.PRNGKey(0))
    masks = linearize.init_masks(model.mask_sites())
    rng = np.random.default_rng(2)
    masks = M.sample_removal_block(rng, masks, 16)
    ctx = {"params": params,
           "batch": {"tokens": np.asarray(
               rng.integers(0, model.cfg.vocab, (2, 17)), np.int32)}}
    _assert_pre_contract(model.make_suffix_eval_fns(), ctx, masks)


def test_suffix_evaluator_context_carries_pre(setup):
    """Construction computes the mask-independent head fold once and ships
    it as ``context["pre"]``; set_context recomputes it."""
    model, params, batch, masks0 = setup
    ctx = {"params": params,
           "batch": {k: np.asarray(v) for k, v in batch.items()}}
    ev = engine.SuffixEvaluator(model.make_suffix_eval_fns(), context=ctx)
    assert "pre" in ev.context
    expect = np.asarray(jax.jit(
        lambda c: model.forward_pre(c["params"], c["batch"]["images"]))(ctx))
    np.testing.assert_array_equal(np.asarray(ev.context["pre"]), expect)
    # swapping the context recomputes the fold from the new params
    params2 = model.init(jax.random.PRNGKey(9))
    ev.set_context({"params": params2, "batch": ctx["batch"]})
    expect2 = np.asarray(jax.jit(
        lambda c: model.forward_pre(c["params"], c["batch"]["images"]))(
            {"params": params2, "batch": ctx["batch"]}))
    np.testing.assert_array_equal(np.asarray(ev.context["pre"]), expect2)
    assert not np.array_equal(expect, expect2)


def test_suffix_sites_and_fractions_are_monotone():
    model = CNN(CNNConfig("tiny", 4, 16, ((8, 1, 1), (16, 1, 2)),
                          stem_channels=8))
    order = model.site_order()
    fr = model.site_prefix_fractions()
    segs = model.site_segments()
    prev = -1.0
    for site in order:
        # suffix consumes exactly the sites in segments >= the cut
        assert model.suffix_sites(site) == tuple(
            s for s in order if segs[s] >= segs[site])
        assert fr[site] >= prev - 1e-12     # deeper cut, larger prefix
        prev = fr[site]
    assert fr[order[0]] == 0.0
    assert fr[order[-1]] > 0.5
    lm = _tiny_lm()
    lfr = lm.site_prefix_fractions()
    assert lfr["h0.ffn"] == 0.0
    assert lfr["t0.ffn"] > lfr["s0.ffn"] > lfr["h0.ffn"]
    assert lm.suffix_sites("s1.ffn") == ("s0.ffn", "s1.ffn", "t0.ffn")
    # per-repeat cuts: deeper repeats reuse a larger prefix; the REAL mask
    # name maps to its repeat-0 segment (the shallowest cut its
    # coordinates can force)
    assert lfr["s0.ffn@1"] > lfr["s0.ffn@0"] == lfr["s0.ffn"]
    # a mid-scan cut still ships the full (R, ·) stack arrays: a stack
    # site's deepest repeat is always at/after any stack cut
    assert lm.suffix_sites("s0.ffn@1") == ("s0.ffn", "s1.ffn", "t0.ffn")
    assert lm.suffix_sites("t0.ffn") == ("t0.ffn",)


# -------------------------------------------------- grouping / planning


def test_group_blocks_by_site():
    masks = {"a": np.ones((4,), np.float32), "b": np.ones((4,), np.float32),
             "c": np.ones((4,), np.float32)}
    _, layout = M._flatten(masks)       # a:[0,4) b:[4,8) c:[8,12)
    rank = {"a": 0, "b": 1, "c": 2}
    indices = np.array([[9, 10],        # earliest c -> rank 2
                        [5, 11],        # earliest b -> rank 1
                        [1, 9],         # earliest a -> rank 0
                        [6, 7],         # rank 1
                        [8, 11]])       # rank 2
    order, groups = M.group_blocks_by_site(indices, layout, rank)
    np.testing.assert_array_equal(order, [2, 1, 3, 0, 4])  # stable in-group
    assert groups == [(0, 0, 1), (1, 1, 3), (2, 3, 5)]
    # empty-candidate edge
    order0, groups0 = M.group_blocks_by_site(
        np.zeros((0, 2), np.int64), layout, rank)
    assert order0.size == 0 and groups0 == []


def test_group_blocks_by_site_repeat_aware():
    """With ``repeat_sites``, a stack coordinate's rank is its repeat-0
    rank plus its repeat row — candidates touching only deep repeats group
    at deeper segments (larger reusable prefixes)."""
    masks = {"h0.ffn": np.ones((4,), np.float32),
             "s0.ffn": np.ones((2, 4), np.float32)}   # R=2, 4 per repeat
    _, layout = M._flatten(masks)      # h0:[0,4) s0:[4,12) repeat-major
    rank = {"h0.ffn": 0, "s0.ffn": 1}
    reps = {"s0.ffn": 2}
    indices = np.array([[8, 9],        # repeat 1 only -> rank 2
                        [4, 10],       # earliest repeat 0 -> rank 1
                        [0, 11],       # head coord -> rank 0
                        [10, 11]])     # repeat 1 -> rank 2
    order, groups = M.group_blocks_by_site(indices, layout, rank,
                                           repeat_sites=reps)
    np.testing.assert_array_equal(order, [2, 1, 0, 3])
    assert groups == [(0, 0, 1), (1, 1, 2), (2, 2, 4)]
    # without repeat_sites every stack coordinate collapses to rank 1
    _, flat_groups = M.group_blocks_by_site(indices, layout, rank)
    assert [g[0] for g in flat_groups] == [0, 1]
    # move_site_ranks agrees coordinate-wise (swap ranks by its shallowest
    # touched coordinate across off ∪ on)
    moves = [M.Move.remove(np.array([8, 9])),
             M.Move.swap(np.array([4]), np.array([10])),
             M.Move.remove(np.array([0, 11])),
             M.Move.remove(np.array([10, 11]))]
    np.testing.assert_array_equal(
        M.move_site_ranks(moves, layout, rank, repeat_sites=reps),
        [2, 1, 0, 2])


def test_sample_removal_indices_within_virtual_repeat_sites():
    """Virtual ``site@r`` names restrict sampling to that repeat's rows of
    the stacked (R, ·) mask array."""
    masks = {"h0.ffn": np.ones((6,), np.float32),
             "s0.ffn": np.ones((2, 6), np.float32)}
    _, layout = M._flatten(masks)      # h0:[0,6) s0:[6,18)
    rng = np.random.default_rng(0)
    idx = M.sample_removal_indices_within(rng, masks, 3, 4, ["s0.ffn@1"],
                                          repeat_sites={"s0.ffn": 2})
    assert idx.shape == (4, 3)
    assert ((idx >= 12) & (idx < 18)).all(), idx    # repeat-1 rows only
    idx0 = M.sample_removal_indices_within(rng, masks, 3, 4, ["s0.ffn@0"],
                                           repeat_sites={"s0.ffn": 2})
    assert ((idx0 >= 6) & (idx0 < 12)).all(), idx0
    # the bare real name still spans every repeat
    idx_all = M.sample_removal_indices_within(rng, masks, 3, 16, ["s0.ffn"],
                                              repeat_sites={"s0.ffn": 2})
    assert ((idx_all >= 6) & (idx_all < 18)).all()
    assert (idx_all < 12).any() and (idx_all >= 12).any()


def test_coalesce_fallback_chunks():
    raw = [("deep", 0, 2), (None, 2, 3), (None, 3, 5), (None, 5, 6),
           ("mid", 6, 8), (None, 8, 9)]
    out = M.coalesce_fallback_chunks(raw, chunk_size=2)
    # the 3 adjacent fallback tails merge into ceil(3/2) chunks; sited
    # chunks and the trailing singleton pass through
    assert out == [("deep", 0, 2), (None, 2, 4), (None, 4, 6),
                   ("mid", 6, 8), (None, 8, 9)]
    # all-fallback plan collapses to chunk_size-sized spans
    assert M.coalesce_fallback_chunks(
        [(None, 0, 2), (None, 2, 4), (None, 4, 5)], 4) == \
        [(None, 0, 4), (None, 4, 5)]
    assert M.coalesce_fallback_chunks([], 4) == []


def test_plan_sited_chunks_never_straddles_and_respects_cost_model():
    model = CNN(CNNConfig("tiny", 4, 16, ((8, 1, 1), (16, 1, 2)),
                          stem_channels=8))
    masks = linearize.init_masks(model.mask_sites())
    flat, layout = M._flatten(masks)
    order_sites = model.site_order()
    deep, shallow = order_sites[-1], order_sites[0]
    rng = np.random.default_rng(0)
    idx = np.concatenate([
        M.sample_removal_indices_within(rng, masks, 8, 5, [deep]),
        M.sample_removal_indices_within(rng, masks, 8, 3, [shallow])])
    # a real (tiny) context: construction computes the mask-independent
    # head fold (SplitEval.pre) from it, so it must be evaluable
    ctx = {"params": model.init(jax.random.PRNGKey(0)),
           "batch": {"images": np.zeros((1, 16, 16, 3), np.float32),
                     "labels": np.zeros((1,), np.int32)}}
    ev = engine.SuffixEvaluator(model.make_suffix_eval_fns(), context=ctx,
                                cost_model=SuffixCostModel(
                                    min_prefix_fraction=0.05, min_chunk=2))
    order, chunks = engine.plan_sited_chunks(ev, idx, layout, chunk_size=2)
    segs = model.site_segments()
    cand_seg = [min(segs[s] for s in (deep,)) for _ in range(5)] + \
               [segs[shallow]] * 3
    for site, s, e in chunks:
        grp = {cand_seg[i] for i in order[s:e]}
        if site is not None:
            # sited chunks share one prefix -> must stay inside a group;
            # coalesced fallback chunks may straddle (no shared prefix)
            assert len(grp) == 1, "sited chunk straddles a segment group"
            assert segs[site] == grp.pop()
    # shallow group (prefix fraction 0) must fall back to the full forward
    shallow_chunks = [c for c in chunks
                     if all(cand_seg[i] == segs[shallow]
                            for i in order[c[1]:c[2]])]
    assert shallow_chunks and all(c[0] is None for c in shallow_chunks)
    # deep group runs in suffix mode except any cost-model-undersized tail
    deep_chunks = [c for c in chunks
                   if all(cand_seg[i] == segs[deep]
                          for i in order[c[1]:c[2]])]
    assert deep_chunks
    for site, s, e in deep_chunks:
        # plan labels chunks with the segment's representative site
        assert (site is not None and segs[site] == segs[deep]) \
            == (e - s >= 2)
    # a prohibitive cost model sends everything down the fallback
    ev_off = engine.SuffixEvaluator(
        model.make_suffix_eval_fns(), context=ctx,
        cost_model=SuffixCostModel(min_prefix_fraction=1.1))
    _, chunks_off = engine.plan_sited_chunks(ev_off, idx, layout, 2)
    assert all(site is None for site, _, _ in chunks_off)


def test_suffix_cost_model_formula():
    cm = SuffixCostModel(min_prefix_fraction=0.05, min_chunk=2)
    assert cm.speedup(0.0, 8) == pytest.approx(1.0)
    assert cm.speedup(1.0, 8) == pytest.approx(8.0)
    assert cm.speedup(0.5, 8) == pytest.approx(8 / 4.5)
    assert not cm.use_suffix(0.9, 1)        # nothing to reuse across n=1
    assert not cm.use_suffix(0.01, 8)       # shallow cut
    assert cm.use_suffix(0.5, 2)


# --------------------------------------------- selection equivalence


@pytest.fixture(scope="module")
def setup():
    model = CNN(CNNConfig("tiny", 4, 16, ((8, 1, 1), (16, 1, 2)),
                          stem_channels=8))
    data = SyntheticImages(ImageDatasetCfg(n_classes=4, image_size=16,
                                           n_train=256, n_test=64))
    params = model.init(jax.random.PRNGKey(0))
    batch = data.train_eval_set(128)
    masks0 = linearize.init_masks(model.mask_sites())
    return model, params, batch, masks0


def _run(model, params, batch, masks0, evaluator, chunk_size=4, adt=0.5):
    total = M.count(masks0)
    cfg = bcd.BCDConfig(b_target=total - 3 * 16, drc=16, rt=8, adt=adt,
                        finetune_every_step=False, seed=3,
                        chunk_size=chunk_size)
    eval_acc = model.make_eval_acc(params, batch)
    return bcd.run_bcd(masks0, cfg, eval_acc, evaluator=evaluator)


def _assert_same_result(a, b):
    for k in a.masks:
        np.testing.assert_array_equal(a.masks[k], b.masks[k])
    assert len(a.history) == len(b.history)
    for ha, hb in zip(a.history, b.history):
        assert (ha.trials, ha.found_early) == (hb.trials, hb.found_early)
        assert ha.best_drop == pytest.approx(hb.best_drop, abs=1e-4)
        assert (ha.budget_before, ha.budget_after) == \
            (hb.budget_before, hb.budget_after)


def _suffix_ev(model, params, batch, prefetch=None, **kw):
    ctx = {"params": params,
           "batch": {k: np.asarray(v) for k, v in batch.items()}}
    ev = engine.SuffixEvaluator(model.make_suffix_eval_fns(), context=ctx,
                                **kw)
    if prefetch is not None:
        ev.prefetch_depth = prefetch
    return ev


@pytest.mark.parametrize("prefetch", [0, 1, 2])
def test_suffix_matches_sequential_bitwise(setup, prefetch):
    """Site-major evaluation + sampling-order selection replay: the suffix
    backend selects bit-identical blocks (and identical trial counts /
    early-exit flags) at every prefetch depth.  chunk_size=3 vs rt=8 forces
    ragged chunks."""
    model, params, batch, masks0 = setup
    seq = _run(model, params, batch, masks0,
               engine.SequentialEvaluator(model.make_eval_acc(params, batch)),
               chunk_size=3)
    suf = _run(model, params, batch, masks0,
               _suffix_ev(model, params, batch, pad_to=3, prefetch=prefetch),
               chunk_size=3)
    _assert_same_result(seq, suf)


def test_suffix_matches_batched_without_early_exit(setup):
    """adt=-1 disables the ADT exit: the full RT argmin path, where every
    candidate is evaluated — suffix vs the full-forward pipelined evaluator
    must agree exactly."""
    model, params, batch, masks0 = setup
    bat = _run(model, params, batch, masks0,
               engine.PipelinedEvaluator(model.make_eval_fn(params, batch),
                                         pad_to=4), adt=-1.0)
    suf = _run(model, params, batch, masks0,
               _suffix_ev(model, params, batch, pad_to=4, prefetch=1),
               adt=-1.0)
    _assert_same_result(bat, suf)


def test_suffix_cost_model_fallback_is_still_equivalent(setup):
    """min_prefix_fraction > 1 sends every chunk down the inner
    full-forward pipeline — selection must be unchanged (the cost model is
    a pure performance policy)."""
    model, params, batch, masks0 = setup
    seq = _run(model, params, batch, masks0,
               engine.SequentialEvaluator(model.make_eval_acc(params, batch)))
    suf = _run(model, params, batch, masks0,
               _suffix_ev(model, params, batch, pad_to=4, prefetch=1,
                          cost_model=SuffixCostModel(
                              min_prefix_fraction=1.1)))
    _assert_same_result(seq, suf)


def test_suffix_site_local_candidates_use_prefix_cache(setup):
    """Deep-site-local chunks run in suffix mode: accuracies match the
    sequential reference and the trie holds a cached prefix for the deep
    segment afterwards.  Unchanged base masks keep the trie warm across
    ``begin_step``; a shallow-site edit drops every deeper entry."""
    model, params, batch, masks0 = setup
    deep = model.site_order()[-1]
    shallow = model.site_order()[0]
    segs = model.site_segments()
    idx = M.sample_removal_indices_within(
        np.random.default_rng(0), masks0, 16, 6, [deep])
    stacked = M.materialize_candidates(masks0, idx)
    ev = _suffix_ev(model, params, batch, pad_to=6)
    ev.begin_step(masks0)
    accs = ev.evaluate(engine.SitedChunk(deep, stacked))
    seq = engine.SequentialEvaluator(model.make_eval_acc(params, batch))
    np.testing.assert_allclose(accs, seq.evaluate(stacked), atol=1e-4)
    assert segs[deep] in ev.trie and ev.trie.misses == 1
    # unchanged base masks: entries survive the next begin_step
    ev.begin_step({k: np.array(v) for k, v in masks0.items()})
    assert segs[deep] in ev.trie
    accs2 = ev.evaluate(engine.SitedChunk(deep, stacked))
    np.testing.assert_array_equal(np.asarray(accs2), np.asarray(accs))
    assert ev.trie.hits >= 1 and ev.trie.misses == 1
    # a shallow-site mask edit invalidates every deeper cached prefix
    edited = {k: np.array(v) for k, v in masks0.items()}
    edited[shallow] = np.array(edited[shallow])
    edited[shallow].flat[0] = 0.0
    ev.begin_step(edited)
    assert len(ev.trie) == 0


def test_suffix_set_context_invalidates_prefix_cache(setup):
    model, params, batch, masks0 = setup
    ev = _suffix_ev(model, params, batch, pad_to=4)
    deep = model.site_order()[-1]
    idx = M.sample_removal_indices_within(
        np.random.default_rng(0), masks0, 16, 4, [deep])
    ev.begin_step(masks0)
    a = ev.evaluate(engine.SitedChunk(
        deep, M.materialize_candidates(masks0, idx)))
    assert len(ev.trie)
    # perturb params through the shared context: results must change and
    # the stale prefix must be dropped
    new_params = jax.tree.map(lambda v: v * 0.5, params)
    ev.set_context({"params": new_params,
                    "batch": {k: np.asarray(v) for k, v in batch.items()}})
    assert len(ev.trie) == 0
    b = ev.evaluate(engine.SitedChunk(
        deep, M.materialize_candidates(masks0, idx)))
    seq = engine.SequentialEvaluator(
        model.make_eval_acc(new_params, batch))
    np.testing.assert_allclose(
        b, seq.evaluate(M.materialize_candidates(masks0, idx)), atol=1e-4)
    assert a.shape == b.shape


def test_suffix_evaluator_validates_inputs(setup):
    model, params, batch, masks0 = setup
    split = model.make_suffix_eval_fns()
    with pytest.raises(ValueError, match="context"):
        engine.SuffixEvaluator(split, context=None)
    with pytest.raises(ValueError, match="context"):
        engine.SuffixEvaluator(split, context={"params": params})
    ctx = {"params": params,
           "batch": {k: np.asarray(v) for k, v in batch.items()}}
    with pytest.raises(ValueError, match="requires a mesh"):
        engine.SuffixEvaluator(split, context=ctx,
                               context_specs=engine.context_batch_specs(ctx))
    ev = engine.SuffixEvaluator(split, context=ctx)
    with pytest.raises(RuntimeError, match="begin_step"):
        ev.evaluate(engine.SitedChunk(
            model.site_order()[-1],
            M.sample_removal_blocks(np.random.default_rng(0), masks0,
                                    4, 2)))


def test_suffix_staged_plan_matches_sequential(setup):
    """A whole site-major plan, sited and fallback chunks, staged ahead at
    the suffix evaluator's depth (its inner evaluator's): the results, put
    back in sampling order, are bit-identical to the sequential
    reference."""
    model, params, batch, masks0 = setup
    ev = _suffix_ev(model, params, batch, pad_to=2)
    assert ev.prefetch_depth == ev._inner.prefetch_depth == 2
    seq = engine.SequentialEvaluator(model.make_eval_acc(params, batch))
    idx = M.sample_removal_indices(np.random.default_rng(3), masks0, 6, 12)
    flat, layout = M._flatten(masks0)
    ev.begin_step(masks0)
    order, chunks = engine.plan_sited_chunks(ev, idx, layout, chunk_size=2)
    gen = engine.materialize_sited(flat, layout, idx, order, chunks)
    accs = np.concatenate(list(engine.evaluate_prefetched(ev, gen)))
    # un-permute the site-major evaluation back to sampling order
    accs_s = np.empty_like(accs)
    accs_s[order] = accs
    ref = seq.evaluate(M.materialize_candidates(masks0, idx))
    np.testing.assert_array_equal(accs_s, ref)
    assert {site is None for site, _, _ in chunks} == {True, False}


# ----------------------------------------- forced multi-device sharding


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core import engine, linearize, masks as M
from repro.data import ImageDatasetCfg, SyntheticImages
from repro.launch import mesh as mesh_lib
from repro.models.resnet import CNN, CNNConfig

model = CNN(CNNConfig("tiny", 4, 8, ((4, 1, 1), (8, 1, 2)),
                      stem_channels=4))
data = SyntheticImages(ImageDatasetCfg(n_classes=4, image_size=8,
                                       n_train=64, n_test=32))
params = model.init(jax.random.PRNGKey(0))
batch = data.train_eval_set(16)
masks0 = linearize.init_masks(model.mask_sites())
mesh = mesh_lib.make_cand_batch_mesh(cand=2, batch=2)
ctx = {"params": params,
       "batch": {k: np.asarray(v) for k, v in batch.items()}}
ev = engine.SuffixEvaluator(model.make_suffix_eval_fns(), context=ctx,
                            context_specs=engine.context_batch_specs(ctx),
                            mesh=mesh, pad_to=4)
seq = engine.SequentialEvaluator(model.make_eval_acc(params, batch))

order, segs = model.site_order(), model.site_segments()
deep = order[-1]
mid = max((s for s in order if segs[s] < segs[deep]), key=lambda s: segs[s])
rng = np.random.default_rng(0)
idx_mid = M.sample_removal_indices_within(rng, masks0, 8, 6, [mid])
idx = M.sample_removal_indices_within(rng, masks0, 8, 6, [deep])
stacked_mid = M.materialize_candidates(masks0, idx_mid)
stacked = M.materialize_candidates(masks0, idx)
ev.begin_step(masks0)
accs_mid = ev.evaluate(engine.SitedChunk(mid, stacked_mid))
np.testing.assert_allclose(accs_mid, seq.evaluate(stacked_mid), atol=1e-4)
# deep chunk extends the cached mid-depth ancestor (segments in between
# only), never recomputing from the input
accs = ev.evaluate(engine.SitedChunk(deep, stacked))
np.testing.assert_allclose(accs, seq.evaluate(stacked), atol=1e-4)
assert ev.trie.extensions == 1 and ev.trie.misses == 1, \
    (ev.trie.extensions, ev.trie.misses)
assert ev.trie.depths() == (segs[mid], segs[deep]), ev.trie.depths()

# every trie entry is batch-sharded (never gathered across "batch"),
# including the one produced by the extension path
for depth, cached in ev.trie.items():
    assert "batch" in str(cached.sharding.spec), (depth, cached.sharding)
    assert not cached.sharding.is_fully_replicated, depth
# the mask-independent head fold rides the context batch-sharded too
pre = ev.context["pre"]
assert "batch" in str(pre.sharding.spec), pre.sharding
assert not pre.sharding.is_fully_replicated, pre.sharding
# fallback (un-sited) chunks ride the inner sharded pipeline (and consume
# the sharded "pre" without gathering)
accs2 = ev.evaluate(engine.SitedChunk(None, stacked))
np.testing.assert_allclose(accs2, seq.evaluate(stacked), atol=1e-4)
print("SUFFIX_MESH_OK")
"""


def test_suffix_prefix_cache_batch_sharded_on_forced_mesh():
    """4 forced host devices, ("cand", "batch") = (2, 2): suffix chunks
    shard candidates over "cand" while every trie entry — including one
    built by the ancestor-extension path — stays batch-sharded and never
    gathers; results match the sequential reference."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SUFFIX_MESH_OK" in out.stdout


# -------------------------------------------------------- compile cache


@pytest.fixture
def restore_compile_cache():
    """Put JAX's persistent-cache settings back after a test enables it,
    so later tests in the same worker do not write to its directory."""
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_compile_cache_enable_and_hit_counter(tmp_path, monkeypatch,
                                              restore_compile_cache):
    """enable() + clear_caches() round trip: the second compile of an
    identical program is served from the persistent cache, and the
    recorder of ``core.tracing`` counts it as ``jax.cache_hit``."""
    from repro.core import tracing
    from repro.launch import compile_cache
    d = str(tmp_path / "cc")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    assert compile_cache.enable() == d
    import jax.numpy as jnp
    with tracing.recording() as rec:
        f = jax.jit(lambda x: (x * 3).sum())
        f(jnp.ones((4, 4)))
        assert compile_cache.entry_count(d) > 0
        before_hits = rec.counts["jax.cache_hit"]
        jax.clear_caches()
        jax.jit(lambda x: (x * 3).sum())(jnp.ones((4, 4)))
    assert rec.counts["jax.cache_hit"] > before_hits
    line = compile_cache.log_line(rec.counts)
    assert "served from the persistent cache" in line
    hits = rec.counts["jax.cache_hit"]
    assert f"{hits}/{hits + rec.counts['jax.cache_miss']} " in line
    assert compile_cache.entry_count(None) == 0


def test_compile_cache_env_dir_is_the_directory(tmp_path, monkeypatch,
                                                restore_compile_cache):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache directory and
    nothing in code replaces it."""
    from repro.launch import compile_cache
    d = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    assert compile_cache.cache_dir() == d
    assert compile_cache.enable() == d
    assert jax.config.jax_compilation_cache_dir == d
    assert os.path.isdir(d)


def test_compile_cache_defaults_to_fixed_checkout_path(
        monkeypatch, restore_compile_cache):
    """Without the variable the cache is <checkout>/.jax_cache — one fixed
    path (the directory is part of the cache key), listed in .gitignore."""
    from repro.launch import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert compile_cache.REPO_CACHE_DIR == want
    assert compile_cache.cache_dir() == want
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(root, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()
