"""Fused suffix megakernels: gate folded into the adjacent matmul/conv.

Contract under test (parity with the unfused pair, docs/bcd_engine.md
"Fused suffix kernels"):

* **Kernel level** — Pallas interpret mode vs the pure-jnp oracles
  (``ref.masked_act_matmul_ref`` / ``ref.masked_act_conv3x3_ref``), swept
  over activation kinds and ragged shapes, and for the matmul over K/N
  tilings (the float32 accumulator across K blocks).
* **Routing level** — the custom-vmap rule lowers a candidate-axis vmap
  to the stacked fused kernel, broadcasting the unbatched cached prefix.
* **Model level** — a full forward traced under
  ``linearize.fused_suffix_route(interpret=True)`` matches the plain
  forward: bitwise for matmul sites (LM FFN), and to float tolerance for
  the CNNs (im2col accumulation order differs from ``lax.conv``, as it
  does on the MXU).
* **Engine level** — a ``SuffixEvaluator`` whose dispatch is forced onto
  the fused interpret kernels still matches the sequential reference.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ArchConfig, Block
from repro.core import engine, linearize, masks as M
from repro.data import ImageDatasetCfg, SyntheticImages
from repro.kernels import ops, ref
from repro.kernels.masked_act import (
    masked_act_conv3x3, masked_act_conv3x3_batched,
    masked_act_matmul_2d, masked_act_matmul_2d_batched)
from repro.models.lm import LM
from repro.models.resnet import CNN, CNNConfig

KINDS = ["relu", "gelu", "silu", "sqrelu"]

# Fused vs unfused: the same gate, then the MXU's (or im2col's) summation
# order in place of lax.conv's — float32 parity to a few ulps of the
# activations' scale, never bitwise (docs/bcd_engine.md, "Fused suffix kernels").
FUSED_RTOL = FUSED_ATOL = 1e-5


# -------------------------------------------------------- matmul kernel


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("with_mul", [False, True])
def test_fused_matmul_matches_oracle(kind, with_mul):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(37, 48)).astype(np.float32))
    m = jnp.asarray((rng.random(48) > 0.5).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(48, 24)).astype(np.float32))
    mul = jnp.asarray(rng.normal(size=(37, 48)).astype(np.float32)) \
        if with_mul else None
    want = ref.masked_act_matmul_ref(x, m, w, mul, kind=kind)
    got = masked_act_matmul_2d(x, m, w, mul, kind=kind, block_rows=16,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape", [(300, 640, 1000), (20, 1280, 640)])
def test_fused_matmul_tiles_k_and_n(shape):
    """K and N past one block: the grid walks N tiles and accumulates K
    tiles in float32 (zero-padded K where no lane-aligned block divides
    it), and still matches the unfused pair."""
    rows, k, n_out = shape
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(rows, k)).astype(np.float32))
    m = jnp.asarray((rng.random(k) > 0.5).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(k, n_out)).astype(np.float32)) \
        * k ** -0.5
    mul = jnp.asarray(rng.normal(size=(rows, k)).astype(np.float32))
    want = ref.masked_act_matmul_ref(x, m, w, mul, kind="silu")
    got = masked_act_matmul_2d(x, m, w, mul, kind="silu", interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_matmul_batched_matches_per_candidate():
    rng = np.random.default_rng(1)
    n = 3
    x = jnp.asarray(rng.normal(size=(n, 10, 32)).astype(np.float32))
    ms = jnp.asarray((rng.random((n, 32)) > 0.5).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
    mul = jnp.asarray(rng.normal(size=(n, 10, 32)).astype(np.float32))
    got = masked_act_matmul_2d_batched(x, ms, w, mul, kind="silu",
                                       block_rows=8, interpret=True)
    for i in range(n):
        one = masked_act_matmul_2d(x[i], ms[i], w, mul[i], kind="silu",
                                   block_rows=8, interpret=True)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(one))


# ---------------------------------------------------------- conv kernel


@pytest.mark.parametrize("kind", ["relu", "gelu"])
@pytest.mark.parametrize("hw", [(8, 8), (9, 7), (16, 16)])
def test_fused_conv3x3_matches_oracle(kind, hw):
    rng = np.random.default_rng(2)
    h, wd = hw
    x = jnp.asarray(rng.normal(size=(2, h, wd, 6)).astype(np.float32))
    m = jnp.asarray((rng.random((h, wd, 6)) > 0.5).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, 6, 5)).astype(np.float32))
    want = ref.masked_act_conv3x3_ref(x, m, w, kind=kind)
    got = masked_act_conv3x3(x, m, w, kind=kind, interpret=True)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_conv3x3_batched_matches_per_candidate():
    rng = np.random.default_rng(3)
    n = 3
    x = jnp.asarray(rng.normal(size=(n, 2, 8, 8, 4)).astype(np.float32))
    ms = jnp.asarray((rng.random((n, 8, 8, 4)) > 0.5).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 6)).astype(np.float32))
    got = masked_act_conv3x3_batched(x, ms, w, interpret=True)
    for i in range(n):
        one = masked_act_conv3x3(x[i], ms[i], w, interpret=True)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(one))


# ----------------------------------------------------------- routing


def test_routed_matmul_vmap_broadcasts_unbatched_prefix():
    """Candidate vmap over masks only (x = the shared cached prefix, mul =
    shared up-branch): the custom-vmap rule must broadcast and lower to the
    stacked kernel, matching the per-candidate fused op exactly."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(5, 32)).astype(np.float32))
    ms = jnp.asarray((rng.random((3, 32)) > 0.5).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
    mul = jnp.asarray(rng.normal(size=(5, 32)).astype(np.float32))
    got = jax.vmap(
        lambda m: ops.masked_act_matmul_routed(x, m, w, mul, kind="gelu",
                                               interpret=True),
        in_axes=0)(ms)
    for i in range(3):
        one = ops.masked_act_matmul_routed(x, ms[i], w, mul, kind="gelu",
                                           interpret=True)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(one))


def test_routed_conv_vmap_broadcasts_unbatched_prefix():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 8, 8, 4)).astype(np.float32))
    ms = jnp.asarray((rng.random((3, 8, 8, 4)) > 0.5).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 3, 4, 4)).astype(np.float32))
    got = jax.vmap(
        lambda m: ops.masked_act_conv3x3_routed(x, m, w, interpret=True),
        in_axes=0)(ms)
    for i in range(3):
        one = ops.masked_act_conv3x3_routed(x, ms[i], w, interpret=True)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(one))


def test_routed_rejects_batched_weights():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
    m = jnp.ones((8,), jnp.float32)
    ws = jnp.asarray(rng.normal(size=(2, 8, 4)).astype(np.float32))
    with pytest.raises(NotImplementedError, match="candidate-shared"):
        jax.vmap(lambda w: ops.masked_act_matmul_routed(
            x, m, w, interpret=True))(ws)


def test_fused_route_hint_is_scoped():
    assert linearize.fused_route_mode() is None
    with linearize.fused_suffix_route(interpret=True):
        assert linearize.fused_route_mode() == "interpret"
        with linearize.fused_suffix_route():
            assert linearize.fused_route_mode() == "device"
        assert linearize.fused_route_mode() == "interpret"
    assert linearize.fused_route_mode() is None


# --------------------------------------------------------- model level


def _masked(model, n_zero, seed=0):
    masks = linearize.init_masks(model.mask_sites())
    return M.sample_removal_block(np.random.default_rng(seed), masks,
                                  n_zero)


def test_cnn_forward_fused_route_bitwise():
    model = CNN(CNNConfig("tiny", 4, 16, ((8, 1, 1), (16, 1, 2)),
                          stem_channels=8))
    params = model.init(jax.random.PRNGKey(0))
    md = M.as_device(_masked(model, 64))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    plain = np.asarray(jax.jit(model.forward)(params, md, x))
    with linearize.fused_suffix_route(interpret=True):
        fused = np.asarray(jax.jit(model.forward)(params, md, x))
    np.testing.assert_allclose(fused, plain, rtol=FUSED_RTOL,
                               atol=FUSED_ATOL)


def test_wide_cnn_forward_fused_route_close():
    # wide blocks fuse relu2 -> conv2 only (relu1 feeds conv1 AND the
    # projection shortcut); im2col accumulation order differs from
    # lax.conv, so parity is float-level, not bitwise
    model = CNN(CNNConfig("wrn-mini", 4, 16,
                          ((8, 1, 1), (16, 1, 2), (16, 1, 2)),
                          stem_channels=8, wide=True))
    params = model.init(jax.random.PRNGKey(0))
    md = M.as_device(_masked(model, 64))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    plain = np.asarray(jax.jit(model.forward)(params, md, x))
    with linearize.fused_suffix_route(interpret=True):
        fused = np.asarray(jax.jit(model.forward)(params, md, x))
    np.testing.assert_allclose(fused, plain, rtol=FUSED_RTOL,
                               atol=FUSED_ATOL)


def _tiny_lm():
    cfg = ArchConfig(
        name="tiny-fused", family="dense", n_layers=6, d_model=32,
        n_heads=2, n_kv_heads=2, d_ff=48, vocab=64, head_dim=16,
        pattern=(Block("dense"), Block("dense")),
        head_blocks=(Block("dense"),), dtype="float32")
    return LM(cfg)


def test_lm_forward_fused_route_bitwise():
    model = _tiny_lm()
    params = model.init(jax.random.PRNGKey(0))
    md = M.as_device(_masked(model, 16))
    rng = np.random.default_rng(0)
    tokens = np.asarray(rng.integers(0, model.cfg.vocab, (2, 9),
                                     dtype=np.int32))
    fwd = jax.jit(lambda p, m, t: model.forward(p, m, t)[0])
    plain = np.asarray(fwd(params, md, tokens))
    with linearize.fused_suffix_route(interpret=True):
        fused = np.asarray(
            jax.jit(lambda p, m, t: model.forward(p, m, t)[0])(
                params, md, tokens))
    np.testing.assert_array_equal(fused, plain)


def test_cnn_split_forward_fused_route_per_site():
    """prefix∘suffix == forward with fusion armed — the composition the
    suffix engine actually traces — to the fused tolerance."""
    model = CNN(CNNConfig("tiny", 4, 16, ((8, 1, 1), (16, 1, 2)),
                          stem_channels=8))
    params = model.init(jax.random.PRNGKey(0))
    md = M.as_device(_masked(model, 64))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    plain = np.asarray(jax.jit(model.forward)(params, md, x))
    with linearize.fused_suffix_route(interpret=True):
        for site in model.site_order():
            def composed(p, m, x, site=site):
                return model.forward_suffix(
                    p, m, model.forward_prefix(p, m, x, site), site)
            out = np.asarray(jax.jit(composed)(params, md, x))
            np.testing.assert_allclose(out, plain, rtol=FUSED_RTOL,
                                       atol=FUSED_ATOL, err_msg=site)


# -------------------------------------------------------- engine level


def test_suffix_evaluator_fused_dispatch_matches_sequential(monkeypatch):
    """Force the fused dispatch on (as on TPU) — the routed ops then run
    the interpret-mode Pallas megakernels inside the suffix vmap; the
    evaluator must still match the sequential reference, and flipping
    ``fused_kernels=False`` must too (fresh jit caches per instance)."""
    monkeypatch.setattr(ops, "fused_dispatch_enabled", lambda: True)
    model = CNN(CNNConfig("tiny", 4, 16, ((8, 1, 1), (16, 1, 2)),
                          stem_channels=8))
    data = SyntheticImages(ImageDatasetCfg(n_classes=4, image_size=16,
                                           n_train=64, n_test=32))
    params = model.init(jax.random.PRNGKey(0))
    batch = data.train_eval_set(32)
    masks0 = linearize.init_masks(model.mask_sites())
    deep = model.site_order()[-1]
    idx = M.sample_removal_indices_within(
        np.random.default_rng(0), masks0, 16, 4, [deep])
    stacked = M.materialize_candidates(masks0, idx)
    ctx = {"params": params,
           "batch": {k: np.asarray(v) for k, v in batch.items()}}
    seq = engine.SequentialEvaluator(model.make_eval_acc(params, batch))
    want = seq.evaluate(stacked)
    for fused in (True, False):
        ev = engine.SuffixEvaluator(model.make_suffix_eval_fns(),
                                    context=ctx, pad_to=4,
                                    fused_kernels=fused)
        ev.begin_step(masks0)
        accs = ev.evaluate(engine.SitedChunk(deep, stacked))
        np.testing.assert_allclose(accs, want, atol=1e-4,
                                   err_msg=f"fused_kernels={fused}")
