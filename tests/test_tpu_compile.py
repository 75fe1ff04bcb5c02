"""Compile rehearsal: every Pallas kernel on a model path, compiled ahead of
time for a described (not attached) TPU v5e chip at the widths the models
run, so a tiling or VMEM refusal fails here and not on the chip.

Interpret mode never sees the TPU compiler's limits; this file does.  The
topology is described inside a module fixture (never at import: one process
at a time may load the TPU library, and every test worker imports this
file), and every test of the file skips when it cannot be described.
Nothing runs, so these tests say nothing about results or times.
"""
import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels import masked_act as K
from repro.kernels import ops

BF16, F32 = jnp.bfloat16, jnp.float32
D_FF, D_MODEL = 5632, 2048          # stablelm-1.6b FFN (configs/stablelm_1p6b)
# DeepSeek-V2-Lite (configs/deepseek_v2_lite): dense FFN and shared experts,
# float32 as its benchmark cell runs them; the cell's eval batch is 2 x 2048
# tokens and its candidate chunk 8
V2_DENSE, V2_SHARED, V2_TOKENS, V2_CHUNK = 10944, 2816, 4096, 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled_text(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# (kernel call, [(shape, dtype) per argument]) at the widths models run:
# ResNet18 at 32x32 with a 128-image eval batch, stablelm at published width
# for a 512-token prefill and a 4-slot decode step.
CASES = {
    "masked_act_2d/resnet18_stem": (
        lambda x, m: K.masked_act_2d(x, m),
        [((128, 32 * 32 * 64), F32), ((32 * 32 * 64,), F32)]),
    "masked_act_2d/stablelm_prefill": (
        lambda x, m: K.masked_act_2d(x, m, kind="silu"),
        [((512, D_FF), BF16), ((D_FF,), F32)]),
    "masked_act_2d/stablelm_decode": (
        lambda x, m: K.masked_act_2d(x, m, kind="silu"),
        [((4, D_FF), BF16), ((D_FF,), F32)]),
    "masked_act_2d_batched/resnet18_stem": (
        lambda x, m: K.masked_act_2d_batched(x, m),
        [((8, 128, 32 * 32 * 64), F32), ((8, 32 * 32 * 64), F32)]),
    **{f"masked_act_conv3x3/resnet18_{hw}x{hw}x{c}": (
        lambda x, m, w: K.masked_act_conv3x3(x, m, w),
        [((128, hw, hw, c), F32), ((hw, hw, c), F32), ((3, 3, c, c), F32)])
       for hw, c in ((32, 64), (16, 128), (8, 256), (4, 512))},
    "masked_act_conv3x3_batched/resnet18_4x4x512": (
        lambda x, m, w: K.masked_act_conv3x3_batched(x, m, w),
        [((8, 128, 4, 4, 512), F32), ((8, 4, 4, 512), F32),
         ((3, 3, 512, 512), F32)]),
    "masked_act_matmul_2d/stablelm_down_proj": (
        lambda x, m, w, u: K.masked_act_matmul_2d(x, m, w, u, kind="silu"),
        [((512, D_FF), BF16), ((D_FF,), F32), ((D_FF, D_MODEL), BF16),
         ((512, D_FF), BF16)]),
    "masked_act_matmul_2d_batched/stablelm_down_proj": (
        lambda x, m, w, u: K.masked_act_matmul_2d_batched(x, m, w, u,
                                                          kind="silu"),
        [((8, 64, D_FF), BF16), ((8, D_FF), F32), ((D_FF, D_MODEL), BF16),
         ((8, 64, D_FF), BF16)]),
    **{f"masked_act_matmul_2d/deepseek_v2_lite_{k}": (
        lambda x, m, w, u: K.masked_act_matmul_2d(x, m, w, u, kind="silu"),
        [((512, f), F32), ((f,), F32), ((f, D_MODEL), F32), ((512, f), F32)])
       for k, f in (("dense", V2_DENSE), ("shared", V2_SHARED))},
    "masked_act_matmul_2d_batched/deepseek_v2_lite_shared": (
        lambda x, m, w, u: K.masked_act_matmul_2d_batched(x, m, w, u,
                                                          kind="silu"),
        [((V2_CHUNK, V2_TOKENS, V2_SHARED), F32), ((V2_CHUNK, V2_SHARED), F32),
         ((V2_SHARED, D_MODEL), F32), ((V2_CHUNK, V2_TOKENS, V2_SHARED), F32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


def test_masked_act_train_step_compiles_for_v5e(one_chip, monkeypatch):
    """value_and_grad through the TPU's hard-mask gate at a ResNet18 site:
    the whole step compiles, and the gate is XLA's (no Pallas call), so
    autodiff of the plain expression is the step's backward."""
    from repro.core import linearize
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    site = linearize.MaskSite((32, 32, 64), "relu")
    step = jax.value_and_grad(
        lambda x, m: jnp.sum(linearize.apply_masked_act(x, m, site) ** 2))
    text = _compiled_text(step, [((128, 32, 32, 64), F32),
                                 ((32, 32, 64), F32)], one_chip)
    assert "tpu_custom_call" not in text


def _v2_lite_share():
    from repro.configs import get_config
    return dataclasses.replace(get_config("deepseek_v2_lite"),
                               dtype="float32").chip_share()


def test_mla_compiles_for_v5e_at_the_cell_shapes(one_chip):
    """Latent attention of a candidate chunk (8 x 2 x 2048 tokens) at
    published widths, queries in chunks."""
    from repro.models import layers, lm
    cfg = _v2_lite_share()
    c = lm._mla_cfg(cfg, cfg.pattern[0])
    p = jax.eval_shape(lambda k: layers.mla_init(k, c, F32),
                       jax.random.PRNGKey(0))
    pos = jnp.broadcast_to(jnp.arange(2048)[None], (2, 2048))
    fn = jax.vmap(lambda p, x: layers.mla_attention(p, c, x, pos),
                  in_axes=(None, 0))
    text = jax.jit(fn).lower(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                    sharding=one_chip), p),
        jax.ShapeDtypeStruct((V2_CHUNK, 2, 2048, D_MODEL), F32,
                             sharding=one_chip)).compile().as_text()
    assert "while" in text       # the query chunks


def test_held_experts_compile_for_v5e_at_the_cell_shapes(one_chip):
    """The held-expert layer (router over 64, 8 experts held, drop-free
    grouped matmuls, shared experts) for a candidate chunk whose masks
    differ: the candidate axis folds into the sorted rows."""
    from repro.core import linearize
    from repro.models import lm, moe
    cfg = _v2_lite_share()
    mc = lm._moe_cfg(cfg)
    p = jax.eval_shape(lambda k: moe.moe_init(k, mc, F32),
                       jax.random.PRNGKey(0))
    site = linearize.MaskSite((8, 1408), "silu")
    shared = linearize.MaskSite((V2_SHARED,), "silu")
    fn = jax.vmap(lambda p, x, m, ms: moe.moe_ffn(
        p, mc, x, m, site, shared_mask=ms, shared_site=shared),
        in_axes=(None, None, 0, 0))
    sd = lambda shape, dt=F32: jax.ShapeDtypeStruct(shape, dt,
                                                    sharding=one_chip)
    text = jax.jit(fn).lower(
        jax.tree.map(lambda a: sd(a.shape, a.dtype), p),
        sd((2, 2048, D_MODEL)), sd((V2_CHUNK, 8, 1408)),
        sd((V2_CHUNK, V2_SHARED))).compile().as_text()
    assert "tpu_custom_call" in text      # the grouped matmuls


def _resnet18_fallback(one_chip, chunk=None):
    """ResNet18 (100 classes) at a 512-image eval batch, as the uniform
    cell runs its full-forward fallback: the split forward, its context
    (params, batch and the cached stem ``pre``) and mask shapes, with a
    leading ``chunk`` axis when given."""
    from repro.models.resnet import CNN, CNNConfig
    model = CNN(CNNConfig.resnet18(100, 32))
    split = model.make_suffix_eval_fns()
    sd = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    ctx = {"params": jax.eval_shape(model.init, jax.random.PRNGKey(0)),
           "batch": {"images": jax.ShapeDtypeStruct((512, 32, 32, 3), F32),
                     "labels": jax.ShapeDtypeStruct((512,), jnp.int32)}}
    ctx = jax.tree.map(sd, {**ctx, "pre": jax.eval_shape(split.pre, ctx)})
    lead = () if chunk is None else (chunk,)
    masks = {k: sd(jax.ShapeDtypeStruct(lead + s.shape, F32))
             for k, s in model.mask_sites().items()}
    return split, ctx, masks, sd


def _bytes_accessed(compiled):
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return cost["bytes accessed"]


def test_looped_fallback_compiles_for_v5e_in_a_quarter_of_the_vmaps_temp(
        one_chip, monkeypatch):
    """The full-forward fallback's chunk program on one chip, ResNet18 at a
    512-image eval batch and a chunk of 8, with the TPU's dispatch: the
    loop over candidates compiles with XLA's gate at every site (no Pallas
    call), while the meshed vmap still lowers each site to the stacked
    kernel; and the loop holds one candidate's activations where the vmap
    holds eight in a candidate-minor layout, so its temp memory is under a
    quarter of the vmapped program's."""
    from repro.core import engine
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    split, ctx, masks, sd = _resnet18_fallback(one_chip, chunk=8)
    # the context here only marks eval_fn as taking one; shapes come below.
    # A mesh of the one described chip gives the evaluator's vmapped program.
    mesh = jax.sharding.Mesh(np.array(list(one_chip.device_set)), ("cand",))
    one, meshed = (engine.PipelinedEvaluator(split.full, pad_to=8,
                                             context={}, mesh=m)
                   for m in (None, mesh))
    looped = one._program.lower(
        masks, sd(jax.ShapeDtypeStruct((), jnp.int32)), ctx).compile()
    vmapped = meshed._program.lower(masks, ctx).compile()
    text = looped.as_text()
    assert "while" in text and "tpu_custom_call" not in text
    assert "tpu_custom_call" in vmapped.as_text()
    assert looped.memory_analysis().temp_size_in_bytes < \
        vmapped.memory_analysis().temp_size_in_bytes / 4


def test_fallback_forward_moves_fewer_bytes_with_xlas_gate_for_v5e(
        one_chip, monkeypatch):
    """One fallback forward (ResNet18, 512 images, from the cached stem
    ``pre``) compiled for a v5e both ways: plain, every gate XLA's and
    fused into the batch norm or residual add that produces its input; and
    under ``stacked_kernel_route`` (the engine's wrapper, a function of its
    own, so the two traces are not one cached trace), whose unbatched call
    runs the Pallas gate.  XLA's count of the plain program's bytes is below
    the routed one's, though the routed count leaves out its Pallas calls'
    own traffic."""
    from repro.core import engine
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    split, ctx, masks, _ = _resnet18_fallback(one_chip)
    plain = jax.jit(split.full).lower(masks, ctx).compile()
    routed = jax.jit(engine._with_stacked_route(split.full)).lower(
        masks, ctx).compile()
    assert "tpu_custom_call" not in plain.as_text()
    assert "tpu_custom_call" in routed.as_text()
    assert _bytes_accessed(plain) < _bytes_accessed(routed)
