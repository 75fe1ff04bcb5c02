"""Pallas TPU kernel: fused masked activation (Network Linearization).

The inner op of the paper — ``y = m·act(x) + (1−m)·g(x)`` — is elementwise but
sits on the critical path of every linearized forward pass (BCD evaluates it
RT times per outer step over the whole train subsample).  On TPU we tile
(block_rows × block_cols) tiles of the flattened (rows, channels) activation
into VMEM, broadcast the per-channel mask tile across rows inside the kernel,
and fuse the replacement branch (identity or degree-2 polynomial) so the mask
select never materializes in HBM.

Lane alignment: block_cols is a multiple of 128 (VPU lane width); block_rows a
multiple of 8 (f32 sublane).  Grid is (rows/block_rows, cols/block_cols).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SQRT_2_OVER_PI = 0.7978845608028654


def _act_tile(x, kind: str):
    if kind == "relu":
        return jnp.maximum(x, 0.0)
    if kind == "gelu":
        c = jnp.asarray(_SQRT_2_OVER_PI, x.dtype)
        return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))
    if kind == "silu":
        return x * (1.0 / (1.0 + jnp.exp(-x)))
    if kind == "sqrelu":
        r = jnp.maximum(x, 0.0)
        return r * r
    raise ValueError(f"unknown activation {kind!r}")


def _masked_act_kernel(x_ref, m_ref, o_ref, *, kind: str):
    x = x_ref[...]
    m = m_ref[...].astype(x.dtype)  # (1, block_cols) -> broadcast over rows
    y = _act_tile(x, kind)
    o_ref[...] = m * y + (1.0 - m) * x


def _masked_act_poly_kernel(x_ref, m_ref, p_ref, o_ref, *, kind: str):
    x = x_ref[...]
    m = m_ref[...].astype(x.dtype)
    p = p_ref[...].astype(x.dtype)  # (3, block_cols)
    y = _act_tile(x, kind)
    lin = p[0:1, :] * x * x + p[1:2, :] * x + p[2:3, :]
    o_ref[...] = m * y + (1.0 - m) * lin


def masked_act_2d(
    x: jax.Array,
    mask: jax.Array,
    poly: jax.Array | None = None,
    *,
    kind: str = "relu",
    block_rows: int = 256,
    block_cols: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused masked activation over a 2D (rows, channels) array.

    mask: (channels,) 0/1.  poly: optional (3, channels) a,b,c coefficients for
    the replacement g(x)=a·x²+b·x+c (AutoReP mode); identity when None.
    Rows/cols need not divide the block sizes — we clamp blocks to the array.
    """
    rows, cols = x.shape
    br = min(block_rows, rows)
    bc = min(block_cols, cols)
    # Pad to block multiples (cheap; elementwise kernel).
    pr = (-rows) % br
    pc = (-cols) % bc
    xp = jnp.pad(x, ((0, pr), (0, pc))) if (pr or pc) else x
    mp = jnp.pad(mask, ((0, pc),)) if pc else mask
    mp = mp.reshape(1, -1)
    grid = (xp.shape[0] // br, xp.shape[1] // bc)

    x_spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    m_spec = pl.BlockSpec((1, bc), lambda i, j: (0, j))
    out_spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))

    if poly is None:
        fn = pl.pallas_call(
            functools.partial(_masked_act_kernel, kind=kind),
            grid=grid,
            in_specs=[x_spec, m_spec],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
            interpret=interpret,
        )
        out = fn(xp, mp)
    else:
        pp = jnp.pad(poly, ((0, 0), (0, pc))) if pc else poly
        p_spec = pl.BlockSpec((3, bc), lambda i, j: (0, j))
        fn = pl.pallas_call(
            functools.partial(_masked_act_poly_kernel, kind=kind),
            grid=grid,
            in_specs=[x_spec, m_spec, p_spec],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
            interpret=interpret,
        )
        out = fn(xp, mp, pp)
    if pr or pc:
        out = out[:rows, :cols]
    return out


# ------------------------------------------------------------ batched masks
#
# BCD's batched candidate engine evaluates a *stack* of N mask candidates in
# one call: x is (N, rows, cols) (the same activations replicated or
# per-candidate), mask is (N, cols) — one mask row per candidate.  We add a
# leading candidate grid dimension with block size 1: each (b, i, j) program
# owns one (block_rows × block_cols) tile of candidate b, and the mask tile
# (1, 1, block_cols) broadcasts over rows exactly like the 2D kernel.  Poly
# coefficients are per-site, not per-candidate, so they are shared across b.


def _masked_act_kernel_b(x_ref, m_ref, o_ref, *, kind: str):
    x = x_ref[...]                       # (1, br, bc)
    m = m_ref[...].astype(x.dtype)       # (1, 1, bc)
    y = _act_tile(x, kind)
    o_ref[...] = m * y + (1.0 - m) * x


def _masked_act_poly_kernel_b(x_ref, m_ref, p_ref, o_ref, *, kind: str):
    x = x_ref[...]                       # (1, br, bc)
    m = m_ref[...].astype(x.dtype)       # (1, 1, bc)
    p = p_ref[...].astype(x.dtype)       # (1, 3, bc) — candidate-shared
    y = _act_tile(x, kind)
    lin = p[:, 0:1, :] * x * x + p[:, 1:2, :] * x + p[:, 2:3, :]
    o_ref[...] = m * y + (1.0 - m) * lin


def masked_act_2d_batched(
    x: jax.Array,
    mask: jax.Array,
    poly: jax.Array | None = None,
    *,
    kind: str = "relu",
    block_rows: int = 256,
    block_cols: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused masked activation over N stacked candidates.

    x: (N, rows, cols); mask: (N, cols) — candidate b uses mask row b.
    poly: optional (3, cols), shared across candidates (AutoReP replacement
    coefficients belong to the site, not the candidate).
    """
    n, rows, cols = x.shape
    assert mask.shape == (n, cols), (mask.shape, x.shape)
    br = min(block_rows, rows)
    bc = min(block_cols, cols)
    pr = (-rows) % br
    pc = (-cols) % bc
    xp = jnp.pad(x, ((0, 0), (0, pr), (0, pc))) if (pr or pc) else x
    mp = jnp.pad(mask, ((0, 0), (0, pc))) if pc else mask
    mp = mp.reshape(n, 1, -1)
    grid = (n, xp.shape[1] // br, xp.shape[2] // bc)

    x_spec = pl.BlockSpec((1, br, bc), lambda b, i, j: (b, i, j))
    m_spec = pl.BlockSpec((1, 1, bc), lambda b, i, j: (b, 0, j))
    out_spec = pl.BlockSpec((1, br, bc), lambda b, i, j: (b, i, j))

    if poly is None:
        fn = pl.pallas_call(
            functools.partial(_masked_act_kernel_b, kind=kind),
            grid=grid,
            in_specs=[x_spec, m_spec],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
            interpret=interpret,
        )
        out = fn(xp, mp)
    else:
        pp = jnp.pad(poly, ((0, 0), (0, pc))) if pc else poly
        pp = pp.reshape(1, 3, -1)
        p_spec = pl.BlockSpec((1, 3, bc), lambda b, i, j: (0, 0, j))
        fn = pl.pallas_call(
            functools.partial(_masked_act_poly_kernel_b, kind=kind),
            grid=grid,
            in_specs=[x_spec, m_spec, p_spec],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
            interpret=interpret,
        )
        out = fn(xp, mp, pp)
    if pr or pc:
        out = out[:, :rows, :cols]
    return out


# ------------------------------------------------------ fused suffix kernels
#
# The suffix engine's hot shape: a masked-activation gate whose output feeds
# straight into a matmul (LM FFN down-projection) or a 3x3 conv (ResNet block
# body).  Unfused, the gate kernel writes the gated tensor to HBM and the
# matmul/conv reads it right back — for shallow cuts that round-trip is most
# of the suffix's byte traffic.  These kernels keep the gated tile in VMEM
# and feed the MXU directly (jnp.dot with a float32 accumulator, per the TPU
# guide).  Replacement is identity-only (poly2 sites keep the unfused pair)
# and weights are candidate-shared.
#
# VMEM footprint: the matmul kernel tiles rows, output columns and the
# contraction (K) axis, so a program holds a (block_rows, block_k) gate tile,
# a (block_k, block_n) weight tile and a (block_rows, block_n) float32
# accumulator whatever K and N are — an LM down-projection's whole (K, N)
# weight does not fit VMEM.  The conv kernel holds one sample's (H, W, Cin)
# site plus the (H*W, 9*Cin) patch matrix and (9*Cin, Cout) weights — sized
# for CIFAR-scale stages (≤32×32×512 f32 ≈ 2 MB), not ImageNet stems.


def _gate(x, m, kind: str):
    return m * _act_tile(x, kind) + (1.0 - m) * x


def _matmul_tile(dim: int, pref: int) -> int:
    """Block size along a tiled matmul axis: the whole axis when it fits
    ``pref`` (a full-extent block is always legal), else the largest of
    pref, pref/2, …, 128 that divides it (lane-aligned), else ``pref`` with
    the axis zero-padded up to a multiple."""
    if dim <= pref:
        return dim
    b = pref
    while b >= 128:
        if dim % b == 0:
            return b
        b //= 2
    return pref


def _masked_act_matmul_kernel(*refs, kind: str, has_mul: bool):
    if has_mul:
        x_ref, m_ref, u_ref, w_ref, o_ref, acc_ref = refs
    else:
        x_ref, m_ref, w_ref, o_ref, acc_ref = refs
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                          # (br, bk) of one candidate
    g = _gate(x, m_ref[0].astype(x.dtype), kind)   # mask (1, bk) over rows
    if has_mul:
        g = g * u_ref[0]
    acc_ref[...] += jnp.dot(g, w_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(3) - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def masked_act_matmul_2d_batched(
    x: jax.Array,
    mask: jax.Array,
    w: jax.Array,
    mul: jax.Array | None = None,
    *,
    kind: str = "relu",
    block_rows: int = 256,
    block_n: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused ``(m·act(x) + (1−m)·x) [· mul] @ w`` over N stacked candidates.

    x: (N, rows, K); mask: (N, K) 0/1 — one mask row per candidate;
    w: (K, N_out) candidate-shared weights; mul: optional (N, rows, K)
    second operand (gated-FFN up branch, multiplied after the gate, before
    the matmul).  Grid (candidate, rows, N_out, K) with K innermost: each
    program adds one (block_rows, block_k) @ (block_k, block_n) product into
    a float32 VMEM accumulator.  The gated tensor never leaves VMEM.
    Zero-padding K is exact: every activation maps 0 to 0.
    """
    n, rows, k = x.shape
    assert mask.shape == (n, k), (mask.shape, x.shape)
    assert w.shape[0] == k, (w.shape, x.shape)
    n_out = w.shape[1]
    br = min(block_rows, rows)
    bn = _matmul_tile(n_out, block_n)
    bk = _matmul_tile(k, block_k)
    pr, pn, pk = (-rows) % br, (-n_out) % bn, (-k) % bk
    xp = jnp.pad(x, ((0, 0), (0, pr), (0, pk))) if (pr or pk) else x
    mp = (jnp.pad(mask, ((0, 0), (0, pk))) if pk else mask).reshape(n, 1, -1)
    wp = jnp.pad(w, ((0, pk), (0, pn))) if (pk or pn) else w
    grid = (n, xp.shape[1] // br, wp.shape[1] // bn, wp.shape[0] // bk)
    x_spec = pl.BlockSpec((1, br, bk), lambda b, i, j, kk: (b, i, kk))
    in_specs = [x_spec,
                pl.BlockSpec((1, 1, bk), lambda b, i, j, kk: (b, 0, kk))]
    args = [xp, mp]
    if mul is not None:
        in_specs.append(x_spec)
        args.append(jnp.pad(mul, ((0, 0), (0, pr), (0, pk)))
                    if (pr or pk) else mul)
    in_specs.append(pl.BlockSpec((bk, bn), lambda b, i, j, kk: (kk, j)))
    args.append(wp)
    out = pl.pallas_call(
        functools.partial(_masked_act_matmul_kernel, kind=kind,
                          has_mul=mul is not None),
        grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec((1, br, bn), lambda b, i, j, kk: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((n, xp.shape[1], wp.shape[1]),
                                       x.dtype),
        scratch_shapes=[pltpu.VMEM((br, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret)(*args)
    return out[:, :rows, :n_out] if (pr or pn) else out


def masked_act_matmul_2d(
    x: jax.Array,
    mask: jax.Array,
    w: jax.Array,
    mul: jax.Array | None = None,
    *,
    kind: str = "relu",
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """One-candidate :func:`masked_act_matmul_2d_batched`: x (rows, K),
    mask (K,), w (K, N_out), mul optional (rows, K)."""
    out = masked_act_matmul_2d_batched(
        x[None], mask[None], w, None if mul is None else mul[None],
        kind=kind, block_rows=block_rows, interpret=interpret)
    return out[0]


def _conv3x3_tile(g, w_flat, *, out_dtype):
    """im2col SAME 3x3 conv of one gated sample g: (H, W, Cin) ->
    (H, W, Cout).

    Static-slice decomposition: 9 unit-stride taps of the 1-padded sample
    concatenated to a (H*W, 9*Cin) patch matrix, one MXU matmul against the
    (9*Cin, Cout) flattened weights.  Tap-major (ky, kx, cin) column order
    matches ``w.reshape(9*Cin, Cout)`` of HWIO weights.
    """
    h, wd, cin = g.shape
    xp = jnp.pad(g, ((1, 1), (1, 1), (0, 0)))
    patches = jnp.concatenate(
        [xp[ky:ky + h, kx:kx + wd, :].reshape(h * wd, cin)
         for ky in range(3) for kx in range(3)], axis=1)
    out = jnp.dot(patches, w_flat, preferred_element_type=jnp.float32)
    return out.astype(out_dtype).reshape(h, wd, -1)


def _masked_act_conv3x3_kernel(x_ref, m_ref, w_ref, o_ref, *, kind: str):
    x = x_ref[0]                          # (H, W, Cin) — one sample
    g = _gate(x, m_ref[...].astype(x.dtype), kind)  # full site mask
    o_ref[0] = _conv3x3_tile(g, w_ref[...], out_dtype=o_ref.dtype)


def _masked_act_conv3x3_kernel_b(x_ref, m_ref, w_ref, o_ref, *, kind: str):
    x = x_ref[0, 0]                       # (H, W, Cin) of (cand, sample)
    g = _gate(x, m_ref[0].astype(x.dtype), kind)    # candidate's mask
    o_ref[0, 0] = _conv3x3_tile(g, w_ref[...], out_dtype=o_ref.dtype)


def masked_act_conv3x3(
    x: jax.Array,
    mask: jax.Array,
    w: jax.Array,
    *,
    kind: str = "relu",
    interpret: bool = False,
) -> jax.Array:
    """Fused gate + SAME stride-1 3x3 conv: x (B, H, W, Cin), mask
    (H, W, Cin) — the paper's full per-pixel site mask, shared over the
    batch — w HWIO (3, 3, Cin, Cout).  Grid is one program per sample."""
    b, h, wd, cin = x.shape
    assert mask.shape == (h, wd, cin), (mask.shape, x.shape)
    assert w.shape[:3] == (3, 3, cin), (w.shape, x.shape)
    cout = w.shape[3]
    fn = pl.pallas_call(
        functools.partial(_masked_act_conv3x3_kernel, kind=kind),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, wd, cin), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((h, wd, cin), lambda i: (0, 0, 0)),
                  pl.BlockSpec((9 * cin, cout), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, h, wd, cout), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, wd, cout), x.dtype),
        interpret=interpret)
    return fn(x, mask, w.reshape(9 * cin, cout))


def masked_act_conv3x3_batched(
    x: jax.Array,
    mask: jax.Array,
    w: jax.Array,
    *,
    kind: str = "relu",
    interpret: bool = False,
) -> jax.Array:
    """Stacked-candidate :func:`masked_act_conv3x3`: x (N, B, H, W, Cin),
    mask (N, H, W, Cin) — one full site mask per candidate; w shared."""
    n, b, h, wd, cin = x.shape
    assert mask.shape == (n, h, wd, cin), (mask.shape, x.shape)
    cout = w.shape[3]
    fn = pl.pallas_call(
        functools.partial(_masked_act_conv3x3_kernel_b, kind=kind),
        grid=(n, b),
        in_specs=[pl.BlockSpec((1, 1, h, wd, cin),
                               lambda c, i: (c, i, 0, 0, 0)),
                  pl.BlockSpec((1, h, wd, cin), lambda c, i: (c, 0, 0, 0)),
                  pl.BlockSpec((9 * cin, cout), lambda c, i: (0, 0))],
        out_specs=pl.BlockSpec((1, 1, h, wd, cout),
                               lambda c, i: (c, i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b, h, wd, cout), x.dtype),
        interpret=interpret)
    return fn(x, mask, w.reshape(9 * cin, cout))
