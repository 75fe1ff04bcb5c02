"""DeepSeek-V2-Lite's block against the plain reference
(``repro.reference.mla_moe``), at a reduced size with seeded random weights
on the CPU: latent attention with YaRN, the drop-free held-expert MoE layer
and its chip shares, a whole forward and its loss gradients, the split
forward at every cut, and the suffix engine's selections.

Tolerances: program and reference both compute in float32 with every
matmul at the highest precision; they differ only in the order of float32
additions (einsum contractions, the grouped matmul against the reference's
dense per-expert loop, the scan against an unrolled loop), which moves a
value by a few units in the last place of the largest term it sums: 1e-5
relative to the largest magnitude of the compared array (``_close``)."""
import dataclasses
import filecmp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import bcd, engine, linearize, masks as M
from repro.models import layers, moe
from repro.models.lm import LM
from repro.reference import mla_moe as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-5


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    np.testing.assert_array_less(np.max(np.abs(got - want)) / scale, tol)


def _cfg(**kw):
    """Reduced DeepSeek-V2-Lite with 16 routed experts at top-6 (so that a
    share of 2 or 4 experts sees several tokens)."""
    return dataclasses.replace(get_config("deepseek_v2_lite").reduced(),
                               n_experts=16, top_k=6, **kw)


def ref_cfg(cfg) -> dict:
    """The reference's configuration (published ``config.json`` keys) of a
    program configuration."""
    y = cfg.yarn
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": cfg.d_ff_expert,
        "n_routed_experts": cfg.experts_held,
        "router_experts": cfg.n_experts, "expert_offset": cfg.expert_offset,
        "n_shared_experts": cfg.d_ff_shared // cfg.d_ff_expert,
        "num_experts_per_tok": cfg.top_k, "norm_topk_prob": cfg.norm_topk,
        "routed_scaling_factor": 1.0, "rms_norm_eps": 1e-6,
        "rope_theta": cfg.pattern[0].rope_theta,
        "rope_scaling": {"type": "yarn", "factor": y.factor,
                         "original_max_position_embeddings":
                         y.original_max_position, "beta_fast": y.beta_fast,
                         "beta_slow": y.beta_slow,
                         "mscale": y.mscale_all_dim,
                         "mscale_all_dim": y.mscale_all_dim},
        "vocab_size": cfg.vocab, "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": len(cfg.head_blocks),
        "tie_word_embeddings": cfg.tie_embeddings}


def _masks(model, seed, live=0.5):
    rng = np.random.default_rng(seed)
    return {k: (rng.random(s.shape) < live).astype(np.float32)
            for k, s in model.mask_sites().items()}


def _tokens(cfg, seed, B=2, S=16):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab, (B, S)), jnp.int32)


def test_reference_copy_is_the_benchmarks():
    assert filecmp.cmp(os.path.join(ROOT, "src/repro/reference/mla_moe.py"),
                       os.path.join(ROOT, "bench/reference/mla_moe.py"),
                       shallow=False)


def test_published_widths_and_chip_share():
    full = get_config("deepseek_v2_lite")
    assert (full.d_model, full.n_heads, full.kv_lora_rank,
            full.qk_nope_head_dim, full.qk_rope_head_dim, full.v_head_dim,
            full.n_experts, full.top_k, full.d_ff_expert, full.d_ff_shared,
            full.d_ff, full.vocab, full.n_layers) == \
        (2048, 16, 512, 128, 64, 128, 64, 6, 1408, 2816, 10944, 102400, 27)
    assert not full.norm_topk and not full.tie_embeddings
    share = full.chip_share()
    assert (share.n_layers, share.n_repeats, share.experts_held,
            share.expert_offset, share.vocab) == (5, 4, 8, 0, 12800)
    assert full.chip_share(shard=3).expert_offset == 24
    # every width is kept
    for f in ("d_model", "n_heads", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "n_experts", "top_k",
              "d_ff_expert", "d_ff_shared", "d_ff"):
        assert getattr(share, f) == getattr(full, f), f
    sites = LM(share).mask_sites()
    assert {k: s.shape for k, s in sites.items()} == {
        "h0.ffn": (10944,), "s0.moe": (4, 8, 1408), "s0.moe_shared": (4, 2816)}
    with pytest.raises(ValueError, match="held"):
        get_config("deepseek_moe_16b").chip_share()


def test_yarn_frequencies_and_scale_match_reference():
    c = get_config("deepseek_v2_lite")
    mc = layers.MLACfg(2048, 16, 512, 128, 64, 128, yarn=c.yarn)
    rc = ref_cfg(c)
    np.testing.assert_allclose(mc.inv_freq(), ref.yarn_inv_freq(rc),
                               rtol=1e-6)
    assert mc.softmax_scale == pytest.approx(ref.softmax_scale(rc))
    assert mc.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)


@pytest.mark.parametrize("q_chunk", [256, 4])
def test_mla_matches_reference(q_chunk):
    """Latent attention with YaRN alone, whole and in query chunks."""
    cfg = _cfg()
    c = dataclasses.replace(
        layers.MLACfg(cfg.d_model, cfg.n_heads, cfg.kv_lora_rank,
                      cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim, yarn=cfg.yarn), q_chunk=q_chunk)
    p = layers.mla_init(jax.random.PRNGKey(0), c, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    with jax.default_matmul_precision("highest"):
        got = layers.mla_attention(p, c, x, pos)
    want = ref.Net(ref_cfg(cfg))._mla(p, x, jnp.float32)
    _close(got, want)


def _moe_setup(n_held, offset, seed=0):
    mc = moe.MoECfg(d_model=64, n_experts=16, top_k=6, d_ff_expert=32,
                    n_shared=1, d_ff_shared=64, dispatch="held",
                    norm_topk=False, n_held=n_held, expert_offset=offset)
    return mc


def _moe_layer(mc, p, x, mask, shared_mask):
    site = linearize.MaskSite((mc.held, mc.d_ff_expert), "silu")
    with jax.default_matmul_precision("highest"):
        return moe.moe_ffn(p, mc, x, mask, site, shared_mask=shared_mask,
                           shared_site=linearize.MaskSite((64,), "silu"))


def test_moe_shares_sum_to_the_uncut_layer():
    """Eight chips' shares of a layer (2 of 16 experts each): the routed
    parts add up to the uncut reference layer, the shared experts counted
    once; each share also matches the reference's share."""
    rng = np.random.default_rng(0)
    whole = _moe_setup(0, 0)
    p = moe.moe_init(jax.random.PRNGKey(0), whole, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
    mask = jnp.asarray((rng.random((16, 32)) < 0.5).astype(np.float32))
    smask = jnp.asarray((rng.random((64,)) < 0.5).astype(np.float32))
    rc = dict(ref_cfg(_cfg()), n_shared_experts=2)
    want = ref.Net(dict(rc, n_routed_experts=16))._moe(
        p, x, mask, smask, jnp.float32)
    with jax.default_matmul_precision("highest"):
        shared = layers.ffn(p["shared"], x, smask,
                            linearize.MaskSite((64,), "silu"))
    total = shared
    for s in range(8):
        mc = _moe_setup(2, 2 * s)
        ps = dict(p, **{k: p[k][2 * s:2 * s + 2]
                        for k in ("w_gate", "w_up", "w_down")})
        y = _moe_layer(mc, ps, x, mask[2 * s:2 * s + 2], smask)
        ref_share = ref.Net(dict(rc, n_routed_experts=2,
                                 expert_offset=2 * s))._moe(
            ps, x, mask[2 * s:2 * s + 2], smask, jnp.float32)
        _close(y, ref_share)
        total = total + (y - shared)
    _close(total, want)
    _close(_moe_layer(whole, p, x, mask, smask), want)


def test_moe_drops_no_token_when_every_token_picks_one_expert():
    """A router that sends every token to expert 5 (and five more): the
    held layer computes all of them, as the reference does."""
    mc = _moe_setup(4, 4)
    p = moe.moe_init(jax.random.PRNGKey(2), mc, jnp.float32)
    p["router"] = p["router"].at[:, 5].set(0.0)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64))
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0)
    p["router"] = p["router"].at[0, 5].set(100.0)
    eidx, _ = moe.route(p["router"], mc, x)
    assert bool(jnp.all(jnp.any(eidx == 5, axis=-1)))
    rows = moe.held_sizes(eidx, mc)
    assert int(rows[1]) == 128 and int(jnp.sum(rows)) == int(
        jnp.sum((eidx >= 4) & (eidx < 8)))
    mask = jnp.ones((4, 32))
    got = _moe_layer(mc, p, x, mask, jnp.ones((64,)))
    rc = dict(ref_cfg(_cfg()), n_shared_experts=2, n_routed_experts=4,
              expert_offset=4)
    _close(got, ref.Net(rc)._moe(p, x, mask, jnp.ones((64,)), jnp.float32))


def _share_model(seed=0):
    cfg = _cfg().chip_share(ways=4, moe_layers=2)
    model = LM(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(seed))


def test_forward_and_loss_gradients_match_reference():
    cfg, model, params = _share_model()
    masks = M.as_device(_masks(model, 1))
    tokens, labels = _tokens(cfg, 2), _tokens(cfg, 3)
    net = ref.Net(ref_cfg(cfg))

    def loss(p):
        lg, _ = model.forward(p, masks, tokens)
        lse = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
        return jnp.mean(lse - gold)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.forward(params, masks, tokens)
        g = jax.grad(loss)(params)
    _close(logits, net.logits(params, masks, tokens))
    g_ref = jax.grad(net.loss)(params, masks, tokens, labels)
    paths = jax.tree_util.tree_flatten_with_path(g)[0]
    for (path, a), b in zip(paths, jax.tree.leaves(g_ref)):
        _close(a, b, 1e-4)
    rows = model.held_expert_rows(params, masks, tokens)
    np.testing.assert_array_equal(rows, net.held_rows(params, masks, tokens))


def test_split_forward_bitwise_at_every_mid_scan_cut():
    cfg, model, params = _share_model(1)
    masks = M.as_device(_masks(model, 4))
    tokens = _tokens(cfg, 5, S=17)
    assert model.site_order() == ("h0.ffn", "s0.moe@0", "s0.moe_shared@0",
                                  "s0.moe@1", "s0.moe_shared@1")
    full = np.asarray(jax.jit(
        lambda p, m, t: model.forward(p, m, t)[0])(params, masks, tokens))
    for site in model.site_order():
        pj = jax.jit(lambda p, m, t, s=site: model.forward_prefix(p, m, t, s))
        sj = jax.jit(lambda p, m, c, s=site: model.forward_suffix(p, m, c, s))
        out = np.asarray(sj(params, masks, pj(params, masks, tokens)))
        np.testing.assert_array_equal(out, full, err_msg=site)


def test_suffix_engine_selects_the_sequential_engines_blocks():
    """Three BCD steps over labels given in the batch: the suffix engine
    (every cut in the expert stack) selects bit-identical blocks."""
    cfg, model, params = _share_model(2)
    tokens = _tokens(cfg, 6, S=16)
    labels = jnp.argmax(model.forward(params, M.as_device(
        linearize.init_masks(model.mask_sites())), tokens)[0], -1)
    batch = {"tokens": np.asarray(tokens), "labels": np.asarray(labels)}
    masks0 = _masks(model, 7)
    masks0["h0.ffn"][:] = 0.0
    total = M.count(masks0)

    def run(evaluator):
        c = bcd.BCDConfig(b_target=total - 3 * 8, drc=8, rt=6, adt=-1.0,
                          finetune_every_step=False, seed=3, chunk_size=4)
        return bcd.run_bcd(masks0, c, model.make_eval_acc(params, batch),
                           evaluator=evaluator)
    seq = run(engine.SequentialEvaluator(model.make_eval_acc(params, batch)))
    suf = run(engine.SuffixEvaluator(
        model.make_suffix_eval_fns(),
        context={"params": params, "batch": batch}, pad_to=4))
    for k in seq.masks:
        np.testing.assert_array_equal(seq.masks[k], suf.masks[k])
    assert [h.best_drop for h in seq.history] == pytest.approx(
        [h.best_drop for h in suf.history], abs=1e-4)
    assert len(seq.history) == 3


def test_cached_forward_and_serving_refuse_mla():
    from repro.launch.serve_loop import ServeLoop
    cfg, model, params = _share_model()
    with pytest.raises(NotImplementedError, match="latent attention"):
        model.init_cache(1, 8)
    with pytest.raises(NotImplementedError, match="latent attention"):
        model._attn_apply(cfg.pattern[0], jax.tree.map(
            lambda a: a[0], params["stack"]["0"]), jnp.zeros((1, 2, 64)),
            jnp.zeros((1, 2), jnp.int32), {"kv": None}, 0)
    with pytest.raises(NotImplementedError, match="latent attention"):
        ServeLoop(model, params, None, [])


@pytest.mark.parametrize("labelled", [False, True])
def test_lm_eval_scores_given_labels(labelled):
    """With ``labels`` in the batch the eval closures read every token and
    score against the labels; without, the next token as before."""
    cfg = get_config("stablelm_1p6b").reduced()
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    masks = M.as_device(linearize.init_masks(model.mask_sites()))
    tokens = _tokens(cfg, 8, S=9)
    batch = {"tokens": tokens}
    if labelled:
        batch["labels"] = _tokens(cfg, 9, S=9)
        lg = model.forward(params, masks, tokens)[0]
        target = batch["labels"]
    else:
        lg = model.forward(params, masks, tokens[:, :-1])[0]
        target = tokens[:, 1:]
    want = float(jnp.mean(jnp.argmax(lg, -1) == target) * 100.0)
    assert float(model.make_param_eval_fn(batch)(masks, params)) == want
    assert float(model.make_joint_eval_fn()(
        masks, {"params": params, "batch": batch})) == want
    split = model.make_suffix_eval_fns()
    ctx = {"params": params, "batch": batch}
    site = split.site_order[1]
    got = split.suffix(site, masks, split.prefix(site, masks, ctx), ctx)
    assert float(got) == pytest.approx(want)
