"""Compile rehearsal: every Pallas kernel on a model path, compiled ahead of
time for a described (not attached) TPU v5e chip at the widths the models
run, so a tiling or VMEM refusal fails here and not on the chip.

Interpret mode never sees the TPU compiler's limits; this file does.  The
topology is described inside a module fixture (never at import: one process
at a time may load the TPU library, and every test worker imports this
file), and every test of the file skips when it cannot be described.
Nothing runs, so these tests say nothing about results or times.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels import masked_act as K
from repro.kernels import ops

BF16, F32 = jnp.bfloat16, jnp.float32
D_FF, D_MODEL = 5632, 2048          # stablelm-1.6b FFN (configs/stablelm_1p6b)


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
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


def test_masked_act_train_step_compiles_for_v5e(one_chip, monkeypatch):
    """value_and_grad through the hard-mask entry at a ResNet18 site: the
    kernel stays in the forward (custom VJP) and the whole step compiles."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    step = jax.value_and_grad(
        lambda x, m: jnp.sum(ops.masked_act_sited(x, m) ** 2))
    text = _compiled_text(step, [((128, 32, 32, 64), F32),
                                 ((32, 32, 64), F32)], one_chip)
    assert "tpu_custom_call" in text
