"""Pure-jnp oracles for every kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _act(x, kind: str):
    if kind == "relu":
        return jnp.maximum(x, 0.0)
    if kind == "gelu":
        # tanh approximation — matches the kernel exactly.
        c = jnp.sqrt(2.0 / jnp.pi).astype(x.dtype)
        return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))
    if kind == "silu":
        return x * (1.0 / (1.0 + jnp.exp(-x)))
    if kind == "sqrelu":  # rwkv6 channel-mix: relu(x)^2
        r = jnp.maximum(x, 0.0)
        return r * r
    raise ValueError(f"unknown activation {kind!r}")


def masked_act_ref(x, mask, kind: str = "relu", poly=None):
    """y = mask * act(x) + (1-mask) * g(x).

    x:    (..., C) activations
    mask: (C,) float 0/1 — per-channel keep mask (broadcast over leading dims)
    poly: None -> g(x) = x (identity / Network Linearization)
          (3, C) -> g(x) = a*x^2 + b*x + c   (AutoReP-style replacement)
    """
    act = _act(x, kind)
    if poly is None:
        lin = x
    else:
        a, b, c = poly[0], poly[1], poly[2]
        lin = a * x * x + b * x + c
    m = mask.astype(x.dtype)
    return m * act + (1.0 - m) * lin


def masked_act_matmul_ref(x, mask, w, mul=None, *, kind: str = "relu"):
    """Oracle for the fused gate→matmul suffix kernel: the unfused pair
    ``masked_act_ref(x, mask) [· mul] @ w`` (identity replacement only —
    poly2 sites never take the fused route).

    x: (..., K); mask: (K,); w: (K, N); mul: optional (..., K) gated-FFN up
    branch, multiplied after the gate, before the matmul.
    """
    g = masked_act_ref(x, mask, kind=kind)
    if mul is not None:
        g = g * mul
    return g @ w


def masked_act_conv3x3_ref(x, mask, w, *, kind: str = "relu"):
    """Oracle for the fused gate→3x3-conv suffix kernel: the unfused pair —
    full-site gate then ``lax.conv_general_dilated`` (SAME, stride 1,
    NHWC/HWIO), exactly the primitives the CNN's unfused forward traces.

    x: (B, H, W, Cin); mask: (H, W, Cin) per-pixel site mask (batch-shared);
    w: (3, 3, Cin, Cout).
    """
    m = mask.astype(x.dtype)
    g = m * _act(x, kind) + (1.0 - m) * x
    return jax.lax.conv_general_dilated(
        g, w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def rwkv6_chunk_ref(r, k, v, w, u, state):
    """One chunk of the RWKV-6 linear-attention recurrence (oracle).

    Shapes (single head):
      r, k, w : (T, K)     v: (T, V)     u: (K,)    state: (K, V)
    Recurrence per token t:
      y_t   = (u ⊙ k_t) (r_t · ·) v_t  + r_t @ S_t
      S_t+1 = diag(w_t) S_t + k_t^T v_t
    Returns (y: (T, V), new_state).
    """
    T = r.shape[0]
    ys = []
    S = state
    for t in range(T):
        rt, kt, vt, wt = r[t], k[t], v[t], w[t]
        cur = (rt * (u * kt)).sum()[None] * vt  # bonus for current token
        y = rt @ S + cur
        S = wt[:, None] * S + kt[:, None] * vt[None, :]
        ys.append(y)
    return jnp.stack(ys), S
