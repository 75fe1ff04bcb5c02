"""Public jit'd entry points for the kernels.

Dispatch policy: on TPU backends the Pallas kernels run compiled; elsewhere
(the CPU) callers get the pure-jnp oracle unless they explicitly ask for
``force_pallas=True`` / ``interpret=True`` (kernel-correctness tests do).
Model code calls these wrappers only — it never touches pallas directly.

Models reach the masked-activation kernels only through the routed
(custom-vmap) entries: the stacked gate under
``core.linearize.stacked_kernel_route`` and the fused gate + conv/matmul
suffix kernels under ``core.linearize.fused_suffix_route``.  Every other
hard-mask gate is ``core.linearize.masked_act_plain``, which XLA fuses into
the op that produces its input; none of these kernels is differentiable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .masked_act import (masked_act_2d, masked_act_2d_batched,
                         masked_act_conv3x3 as _fused_conv3x3,
                         masked_act_conv3x3_batched as _fused_conv3x3_b,
                         masked_act_matmul_2d, masked_act_matmul_2d_batched)
from .rwkv6_scan import rwkv6_scan as _rwkv6_pallas


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def fused_dispatch_enabled() -> bool:
    """Whether the fused suffix megakernels run natively here (models gate
    their fused-route branches on this; interpret-mode tests bypass it)."""
    return _use_pallas()


def _per_shard(kernel, args, roles):
    """``kernel(*args)``, run on each device's shard under a multi-device
    mesh.

    Mosaic kernels cannot be partitioned automatically, so where a caller
    traces under ``jax.set_mesh`` (the BCD engines over a mesh) the kernel
    runs inside ``shard_map``.  ``roles`` names each argument's leading
    axes: ``"nr"`` (candidates, rows, …), ``"n"`` (candidates, …), ``"r"``
    (rows, …) or ``None`` (replicated); ``None`` arguments are dropped.
    The split follows the engines' ``("cand", "batch")`` convention: the
    candidate axis spans every mesh axis when the mesh size divides it (the
    joint layout), else the non-``"batch"`` axes with rows over
    ``"batch"``.  Every kernel here is elementwise or per-sample over those
    axes, so any split computes the same values; where the operands sit
    elsewhere, ``shard_map`` moves them.
    """
    from jax.sharding import PartitionSpec as P
    roles = [r for a, r in zip(args, roles) if a is not None]
    args = [a for a in args if a is not None]
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return kernel(*args)
    axes = tuple(mesh.axis_names)
    batch = "batch" if "batch" in axes else None
    if roles[0] == "nr" and args[0].shape[0] % mesh.size == 0:
        cand, batch = axes, None
    else:
        cand = tuple(a for a in axes if a != "batch")
    spec = {"nr": P(cand, batch), "n": P(cand), "r": P(batch), None: P()}
    return jax.shard_map(kernel, in_specs=tuple(spec[r] for r in roles),
                         out_specs=spec[roles[0]], check_vma=False)(*args)


def masked_act(x, mask, *, kind: str = "relu", poly=None,
               force_pallas: bool = False, interpret: bool = False):
    """y = mask·act(x) + (1−mask)·g(x) over (..., C) with per-channel mask.

    Accepts any leading shape; flattens to (rows, C) for the kernel.
    """
    if not (force_pallas or _use_pallas()):
        return ref.masked_act_ref(x, mask, kind=kind, poly=poly)
    shape = x.shape
    interpret = interpret or not _use_pallas()
    out = _per_shard(
        lambda *a: masked_act_2d(*a, kind=kind, interpret=interpret),
        (x.reshape(-1, shape[-1]), mask, poly), ("r", None, None))
    return out.reshape(shape)


def masked_act_sited(x, mask, *, kind: str = "relu", poly=None, **kw):
    """Masked activation where the mask covers the full *site* shape.

    For CNNs the paper's mask is per (H, W, C) location shared over batch:
    x: (B, *site), mask: (*site).  Flattens site dims into the channel axis.
    """
    rows = int(x.size // mask.size)
    x2 = x.reshape(rows, mask.size)
    p2 = None if poly is None else poly.reshape(3, mask.size)
    out = masked_act(x2, mask.reshape(-1), kind=kind, poly=p2, **kw)
    return out.reshape(x.shape)


def masked_act_batched(x, masks, *, kind: str = "relu", poly=None,
                       force_pallas: bool = False, interpret: bool = False):
    """Stacked-candidate masked activation (BCD's batched trial engine).

    x: (N, ..., C) — leading axis is the candidate axis; masks: (N, C), one
    per-channel mask row per candidate.  poly: optional (3, C), shared across
    candidates.  Flattens the middle dims to rows for the batched kernel.
    """
    n = masks.shape[0]
    assert x.shape[0] == n, (x.shape, masks.shape)
    if not (force_pallas or _use_pallas()):
        m = masks.reshape((n,) + (1,) * (x.ndim - 2) + (masks.shape[-1],))
        return ref.masked_act_ref(x, m, kind=kind, poly=poly)
    shape = x.shape
    interpret = interpret or not _use_pallas()
    out = _per_shard(
        lambda *a: masked_act_2d_batched(*a, kind=kind, interpret=interpret),
        (x.reshape(n, -1, shape[-1]), masks, poly), ("nr", "n", None))
    return out.reshape(shape)


def masked_act_sited_batched(x, masks, *, kind: str = "relu", poly=None,
                             **kw):
    """Batched :func:`masked_act_sited`: stacked site masks.

    x: (N, B, *site) activations per candidate; masks: (N, *site) — flattens
    site dims into the channel axis, candidates stay the leading axis.
    """
    n = masks.shape[0]
    site_size = int(masks.size // n)
    x3 = x.reshape(n, -1, site_size)
    p2 = None if poly is None else poly.reshape(3, site_size)
    out = masked_act_batched(x3, masks.reshape(n, site_size), kind=kind,
                             poly=p2, **kw)
    return out.reshape(x.shape)


# --------------------------------------------------- candidate-vmap routing
#
# The BCD candidate engines (core.engine) evaluate a chunk of masks as
# jit(vmap(eval_fn)): inside the model forward every mask site then carries a
# hidden candidate batch dim.  Plain vmap of masked_act_sited would batch the
# per-candidate pallas_call's grid; the wrappers below attach a
# jax.custom_batching.custom_vmap rule that instead lowers the whole batched
# site to the stacked kernel (masked_act_2d_batched) — one pallas_call owning
# the (N, rows, cols) tiling, with the mask row broadcast per candidate
# inside VMEM.  custom_vmap does not support differentiation, so this entry
# is opt-in (core.linearize.stacked_kernel_route); unbatched, it runs the
# per-candidate kernel.
#
# Suffix entry (the prefix-reuse engine, core.engine.SuffixEvaluator): the
# vmapped *suffix* forward receives the cached prefix activation with
# in_axes=None, so at the cut segment's first mask site the rule sees a
# batched mask over an UNBATCHED x.  _to_batched broadcasts x across the
# candidate axis before handing the site to the stacked kernel — the one
# extra layout the split forward needs (tests/test_kernels.py pins it).


@functools.lru_cache(maxsize=None)
def _routed_sited(kind: str, interpret: bool, has_poly: bool):
    from jax import custom_batching

    def _to_batched(axis_size, xb, mb, pb, x, mask, poly):
        if pb:
            raise NotImplementedError(
                "poly coefficients are per-site, not per-candidate; a "
                "batched poly axis has no stacked-kernel layout")
        if not xb:        # mask-independent activations (e.g. the first site)
            x = jnp.broadcast_to(x[None], (axis_size,) + x.shape)
        if not mb:
            mask = jnp.broadcast_to(mask[None], (axis_size,) + mask.shape)
        out = masked_act_sited_batched(x, mask, kind=kind, poly=poly,
                                       force_pallas=True, interpret=interpret)
        return out, True

    if has_poly:
        @custom_batching.custom_vmap
        def f(x, mask, poly):
            return masked_act_sited(x, mask, kind=kind, poly=poly,
                                    force_pallas=True, interpret=interpret)

        @f.def_vmap
        def _rule(axis_size, in_batched, x, mask, poly):
            return _to_batched(axis_size, in_batched[0], in_batched[1],
                               in_batched[2], x, mask, poly)
    else:
        @custom_batching.custom_vmap
        def f(x, mask):
            return masked_act_sited(x, mask, kind=kind,
                                    force_pallas=True, interpret=interpret)

        @f.def_vmap
        def _rule(axis_size, in_batched, x, mask):
            return _to_batched(axis_size, in_batched[0], in_batched[1],
                               False, x, mask, None)
    return f


def masked_act_sited_routed(x, mask, *, kind: str = "relu", poly=None,
                            interpret: bool = False):
    """:func:`masked_act_sited` with a custom-vmap rule: under a candidate
    axis vmap (the batched/sharded/pipelined BCD engines) the site lowers to
    the stacked Pallas kernel instead of a vmapped per-candidate grid.

    TPU-path only (callers dispatch; the kernel always runs, with
    ``interpret=True`` for off-TPU tests).  Not differentiable: training
    forwards take ``core.linearize.masked_act_plain``.
    """
    f = _routed_sited(kind, bool(interpret), poly is not None)
    return f(x, mask) if poly is None else f(x, mask, poly)


MASKED_ACT_FUSED_KINDS = ("relu", "gelu", "silu", "sqrelu")


def masked_act_matmul(x, mask, w, mul=None, *, kind: str = "relu",
                      force_pallas: bool = False, interpret: bool = False):
    """Fused ``gate(x) [· mul] @ w`` — the suffix megakernel for a masked
    activation feeding a matmul (LM FFN down-projection).

    x: (..., K); mask: (K,); w: (K, N) candidate-shared; mul: optional
    (..., K).  Off-TPU (without force) this is the unfused oracle — the
    exact primitives the plain forward traces, so CPU dispatch is bitwise
    inert.
    """
    if not (force_pallas or _use_pallas()):
        return ref.masked_act_matmul_ref(x, mask, w, mul, kind=kind)
    shape = x.shape
    interpret = interpret or not _use_pallas()
    u2 = None if mul is None else mul.reshape(-1, shape[-1])
    out = _per_shard(
        lambda *a: masked_act_matmul_2d(*a, kind=kind, interpret=interpret),
        (x.reshape(-1, shape[-1]), mask, w, u2), ("r", None, None, "r"))
    return out.reshape(shape[:-1] + (w.shape[-1],))


def masked_act_matmul_batched(x, masks, w, mul=None, *, kind: str = "relu",
                              force_pallas: bool = False,
                              interpret: bool = False):
    """Stacked-candidate :func:`masked_act_matmul`: x (N, ..., K), masks
    (N, K) — one mask row per candidate — w shared, mul optional
    (N, ..., K)."""
    n = masks.shape[0]
    assert x.shape[0] == n, (x.shape, masks.shape)
    if not (force_pallas or _use_pallas()):
        m = masks.reshape((n,) + (1,) * (x.ndim - 2) + (masks.shape[-1],))
        g = ref.masked_act_ref(x, m, kind=kind)
        if mul is not None:
            g = g * mul
        return g @ w
    shape = x.shape
    interpret = interpret or not _use_pallas()
    u3 = None if mul is None else mul.reshape(n, -1, shape[-1])
    out = _per_shard(
        lambda *a: masked_act_matmul_2d_batched(*a, kind=kind,
                                                interpret=interpret),
        (x.reshape(n, -1, shape[-1]), masks, w, u3), ("nr", "n", None, "nr"))
    return out.reshape(shape[:-1] + (w.shape[-1],))


def masked_act_conv3x3(x, mask, w, *, kind: str = "relu",
                       force_pallas: bool = False, interpret: bool = False):
    """Fused ``conv3x3(gate(x))`` — the suffix megakernel for a CNN's
    masked ReLU feeding a SAME stride-1 3x3 conv.

    x: (B, H, W, Cin); mask: (H, W, Cin) full per-pixel site mask; w HWIO.
    Off-TPU (without force) this is the unfused oracle (gate +
    lax.conv)."""
    if not (force_pallas or _use_pallas()):
        return ref.masked_act_conv3x3_ref(x, mask, w, kind=kind)
    interpret = interpret or not _use_pallas()
    return _per_shard(
        lambda *a: _fused_conv3x3(*a, kind=kind, interpret=interpret),
        (x, mask, w), ("r", None, None))


def masked_act_conv3x3_batched(x, masks, w, *, kind: str = "relu",
                               force_pallas: bool = False,
                               interpret: bool = False):
    """Stacked-candidate :func:`masked_act_conv3x3`: x (N, B, H, W, Cin),
    masks (N, H, W, Cin), w shared."""
    n = masks.shape[0]
    assert x.shape[0] == n, (x.shape, masks.shape)
    if not (force_pallas or _use_pallas()):
        return jax.vmap(lambda x_i, m_i: ref.masked_act_conv3x3_ref(
            x_i, m_i, w, kind=kind))(x, masks)
    interpret = interpret or not _use_pallas()
    return _per_shard(
        lambda *a: _fused_conv3x3_b(*a, kind=kind, interpret=interpret),
        (x, masks, w), ("nr", "n", None))


# Routed (custom_vmap) fused entries: same contract as
# masked_act_sited_routed — under the suffix engine's candidate vmap the
# whole fused site lowers to the stacked kernel, broadcasting an unbatched
# x/mul (the cached prefix at the cut site) across the candidate axis.
# Weights are always candidate-shared (ctx rides with in_axes=None).


def _bcast_cand(axis_size, batched, v):
    return v if batched else jnp.broadcast_to(v[None],
                                              (axis_size,) + v.shape)


@functools.lru_cache(maxsize=None)
def _routed_matmul(kind: str, interpret: bool, has_mul: bool):
    from jax import custom_batching

    if has_mul:
        @custom_batching.custom_vmap
        def f(x, mask, w, mul):
            return masked_act_matmul(x, mask, w, mul, kind=kind,
                                     force_pallas=True, interpret=interpret)

        @f.def_vmap
        def _rule(axis_size, in_batched, x, mask, w, mul):
            xb, mb, wb, ub = in_batched
            if wb:
                raise NotImplementedError(
                    "fused-matmul weights are candidate-shared; a batched "
                    "weight axis has no stacked-kernel layout")
            x = _bcast_cand(axis_size, xb, x)
            mask = _bcast_cand(axis_size, mb, mask)
            mul = _bcast_cand(axis_size, ub, mul)
            out = masked_act_matmul_batched(x, mask, w, mul, kind=kind,
                                            force_pallas=True,
                                            interpret=interpret)
            return out, True
    else:
        @custom_batching.custom_vmap
        def f(x, mask, w):
            return masked_act_matmul(x, mask, w, kind=kind,
                                     force_pallas=True, interpret=interpret)

        @f.def_vmap
        def _rule(axis_size, in_batched, x, mask, w):
            xb, mb, wb = in_batched
            if wb:
                raise NotImplementedError(
                    "fused-matmul weights are candidate-shared; a batched "
                    "weight axis has no stacked-kernel layout")
            x = _bcast_cand(axis_size, xb, x)
            mask = _bcast_cand(axis_size, mb, mask)
            out = masked_act_matmul_batched(x, mask, w, kind=kind,
                                            force_pallas=True,
                                            interpret=interpret)
            return out, True
    return f


def masked_act_matmul_routed(x, mask, w, mul=None, *, kind: str = "relu",
                             interpret: bool = False):
    """:func:`masked_act_matmul` with a custom-vmap rule lowering a
    candidate-axis vmap to the stacked fused kernel.  Not differentiable —
    suffix-engine tracing only (``linearize.fused_suffix_route``)."""
    f = _routed_matmul(kind, bool(interpret), mul is not None)
    return f(x, mask, w) if mul is None else f(x, mask, w, mul)


@functools.lru_cache(maxsize=None)
def _routed_conv3x3(kind: str, interpret: bool):
    from jax import custom_batching

    @custom_batching.custom_vmap
    def f(x, mask, w):
        return masked_act_conv3x3(x, mask, w, kind=kind,
                                  force_pallas=True, interpret=interpret)

    @f.def_vmap
    def _rule(axis_size, in_batched, x, mask, w):
        xb, mb, wb = in_batched
        if wb:
            raise NotImplementedError(
                "fused-conv weights are candidate-shared; a batched weight "
                "axis has no stacked-kernel layout")
        x = _bcast_cand(axis_size, xb, x)
        mask = _bcast_cand(axis_size, mb, mask)
        out = masked_act_conv3x3_batched(x, mask, w, kind=kind,
                                         force_pallas=True,
                                         interpret=interpret)
        return out, True
    return f


def masked_act_conv3x3_routed(x, mask, w, *, kind: str = "relu",
                              interpret: bool = False):
    """:func:`masked_act_conv3x3` with a custom-vmap rule lowering a
    candidate-axis vmap to the stacked fused kernel.  Not differentiable —
    suffix-engine tracing only (``linearize.fused_suffix_route``)."""
    return _routed_conv3x3(kind, bool(interpret))(x, mask, w)


def rwkv6(r, k, v, w, u, state, *, chunk: int = 32,
          force_pallas: bool = False, interpret: bool = False):
    """Chunked rwkv6 scan over (BH, T, K/V); falls back to a lax.scan oracle."""
    if force_pallas or _use_pallas():
        return _rwkv6_pallas(r, k, v, w, u, state, chunk=chunk,
                             interpret=interpret or not _use_pallas())
    return _rwkv6_scan_jnp(r, k, v, w, u, state)


@jax.jit
def _rwkv6_scan_jnp(r, k, v, w, u, state):
    """Vectorized (over BH) chunk-free oracle using lax.scan on tokens."""
    def head(r, k, v, w, u, s0):
        def step(S, inp):
            rt, kt, vt, wt = inp
            y = rt @ S + (rt * (u * kt)).sum() * vt
            S = wt[:, None] * S + kt[:, None] * vt[None, :]
            return S, y
        S, ys = jax.lax.scan(step, s0, (r, k, v, w))
        return ys, S
    return jax.vmap(head)(r, k, v, w, u, state)
