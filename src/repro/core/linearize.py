"""Mask ↔ model glue: declares *mask sites* and applies masked activations.

A model exposes ``mask_sites() -> {name: MaskSite}``; the linearization engine
builds the mask tree, and the model's forward applies ``apply_masked_act`` at
each site.  This keeps the paper's algorithm (core.bcd / core.snl) fully
model-agnostic: BCD only ever sees the mask tree.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Tuple

import jax.numpy as jnp

from repro.kernels import ops
from . import masks as M

_ROUTE_STATE = threading.local()


@contextlib.contextmanager
def stacked_kernel_route(on: bool = True):
    """Trace-time hint (thread-local): inside this context, the hard-mask
    TPU dispatch in :func:`apply_masked_act` emits the custom-vmap routed op
    (``ops.masked_act_sited_routed``), so a candidate-axis vmap — the
    batched/sharded/pipelined engines in ``core.engine`` — lowers every mask
    site to the stacked Pallas kernel (``masked_act_2d_batched``) instead of
    vmapping the per-candidate kernel's grid.  Off by default: custom_vmap
    does not support differentiation, and training forwards must keep the
    plain kernel."""
    prev = getattr(_ROUTE_STATE, "on", False)
    _ROUTE_STATE.on = on
    try:
        yield
    finally:
        _ROUTE_STATE.on = prev


def stacked_route_active() -> bool:
    return getattr(_ROUTE_STATE, "on", False)


@contextlib.contextmanager
def fused_suffix_route(interpret: bool = False):
    """Trace-time hint (thread-local): inside this context, models fold a
    hard-mask activation gate into the adjacent conv/matmul via the fused
    Pallas entry points (``ops.masked_act_conv3x3_routed`` /
    ``ops.masked_act_matmul_routed``) instead of the gate-then-dispatch
    pair — the gated tensor never round-trips HBM.  The suffix engine
    (``core.engine.SuffixEvaluator``) arms this while tracing its suffix
    jits; soft/poly sites and non-TPU backends fall through to the plain
    path.  ``interpret=True`` forces the fused kernels in Pallas interpret
    mode regardless of backend — CPU parity tests only."""
    prev = getattr(_ROUTE_STATE, "fused", None)
    _ROUTE_STATE.fused = "interpret" if interpret else "device"
    try:
        yield
    finally:
        _ROUTE_STATE.fused = prev


def fused_route_mode() -> Optional[str]:
    """``None`` (off), ``"device"`` (fuse where Pallas runs natively), or
    ``"interpret"`` (force interpret-mode kernels — tests)."""
    return getattr(_ROUTE_STATE, "fused", None)


# Activation kinds with a masked lowering (ref path + Pallas kernels).
# Families register their gates against this set: dense/moe FFNs use the
# config's act (relu/gelu/silu), expert FFNs share the routed experts'
# (E, F) site, rwkv6's channel mix registers 'sqrelu' (relu(x)²), mamba
# registers 'silu' on the gated inner width.
KINDS = ("relu", "gelu", "silu", "sqrelu")
REPLACEMENTS = ("identity", "poly2")


@dataclasses.dataclass(frozen=True)
class MaskSite:
    """One maskable nonlinearity site.

    shape: the mask shape (shared over batch / sequence).  CNNs use the full
    (H, W, C) activation-site shape (paper's per-pixel masks); transformers use
    per-channel (n_layers_in_stack, d_ff) for a scanned stack.
    kind:  activation at the site ('relu' | 'gelu' | 'silu' | 'sqrelu').
    replacement: 'identity' (Network Linearization) or 'poly2' (AutoReP).

    Validated at registration: a typo'd kind would otherwise only surface
    at trace time, deep inside the kernel dispatch of whichever backend
    first evaluates the site.
    """
    shape: Tuple[int, ...]
    kind: str = "relu"
    replacement: str = "identity"

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(
                f"unknown activation kind {self.kind!r} (one of {KINDS})")
        if self.replacement not in REPLACEMENTS:
            raise ValueError(
                f"unknown replacement {self.replacement!r} "
                f"(one of {REPLACEMENTS})")
        if not self.shape or any(int(d) <= 0 for d in self.shape):
            raise ValueError(f"mask shape must be non-empty positive dims, "
                             f"got {self.shape!r}")


def init_masks(sites: Dict[str, MaskSite]) -> M.MaskTree:
    return M.full_masks({k: s.shape for k, s in sites.items()})


def init_poly(sites: Dict[str, MaskSite]) -> Dict[str, jnp.ndarray]:
    """AutoReP poly2 coefficients per site, initialized near identity:
    g(x) = 0·x² + 1·x + 0."""
    out = {}
    for k, s in sites.items():
        if s.replacement == "poly2":
            p = jnp.zeros((3,) + s.shape, dtype=jnp.float32)
            p = p.at[1].set(1.0)
            out[k] = p
    return out


def _apply_share_ties(x, mask, out):
    """Override share-tied coordinates (``masks.TIE``) in a hard-masked
    activation output.

    A tied coordinate keeps its gate but reuses the *sign decision* of its
    driver — the previous coordinate along the site's last axis (DeepShare-
    style neighbor sharing: one garbled-circuit comparison serves both
    coordinates in the PI protocol, which is why ``masks.relu_cost`` does
    not bill ties).  ``out = x * H(x_driver)`` where H is the Heaviside
    step on the driver's pre-activation.  Binary masks make ``tied``
    all-False and the ``where`` selects ``out`` everywhere — bit-identical
    to the pre-move-vocabulary forward, so kernel-parity and backend-
    equivalence contracts are unchanged.

    Note: the fused conv/matmul suffix kernels (``fused_suffix_route``)
    bypass this wrapper — run share-enabled configs with
    ``fused_kernels=False`` on the suffix backend (inert off-TPU, where
    the fused route never arms).
    """
    tied = (mask > 0.5) & (mask < 0.9)
    drv = (jnp.roll(x, 1, axis=-1) > 0).astype(x.dtype)
    return jnp.where(tied, x * drv, out)


def masked_act_plain(x, mask, site: MaskSite, poly=None, soft: bool = False):
    """The masked activation as one broadcast expression (no kernel):
    ``m·act(x) + (1−m)·lin(x)``, ``lin`` the identity or the poly2
    replacement; ``mask`` (and ``poly``) broadcast against ``x`` — a mask
    per row serves the held-expert MoE's sorted rows."""
    from repro.kernels import ref
    if soft:
        mask = jnp.clip(mask, 0.0, 1.0)
    y = ref._act(x, site.kind)
    if poly is None:
        lin = x
    else:
        a, b, c = poly[0], poly[1], poly[2]
        lin = a * x * x + b * x + c
    m = mask.astype(x.dtype)
    out = m * y + (1.0 - m) * lin
    return out if soft else _apply_share_ties(x, mask, out)


def apply_masked_act(x, mask, site: MaskSite, poly=None, soft: bool = False):
    """Apply the (possibly soft, for SNL) masked activation at a site.

    x: (batch..., *site.shape) — site shape must be the trailing dims.
    soft=True keeps real-valued masks differentiable (SNL's relaxation);
    hard masks route through the fused kernel wrapper.  Hard masks may
    carry share-tied coordinates (``masks.TIE``), overridden by
    :func:`_apply_share_ties`; soft mode treats every real value as an SNL
    relaxation weight and never ties.
    """
    p = None
    if poly is not None and (soft or site.replacement == "poly2"):
        p = poly
    if soft:
        mask = jnp.clip(mask, 0.0, 1.0)
    if soft or not ops._use_pallas():
        # Direct broadcast application — NO reshape.  Flattening the site
        # dims (e.g. an MoE (E, F) mask) merges a model-sharded axis into a
        # mixed one and forces GSPMD to fully rematerialize the activation
        # (EXPERIMENTS.md §Perf, mixtral hillclimb).  Pallas needs the 2D
        # layout, but it only runs on TPU where the kernel owns the tiling.
        return masked_act_plain(x, mask, site, poly=p, soft=soft)
    if stacked_route_active():
        out = ops.masked_act_sited_routed(x, mask, kind=site.kind, poly=p)
    else:
        out = ops.masked_act_sited(x, mask, kind=site.kind, poly=p)
    return _apply_share_ties(x, mask, out)
