"""Shared transformer layers: norms, RoPE (and YaRN), GQA attention, latent
attention (MLA), gated FFN.

All functions are pure: params are nested dicts of jnp arrays; mask trees ride
alongside (core.linearize).  Attention is q-chunked (flash-style, full-row
softmax per chunk) above a sequence threshold so 32k prefills never
materialize (S, S) score tensors.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import linearize

# ---------------------------------------------------------------- norms


def rmsnorm_init(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p, x, eps=1e-6):
    # variance reduce in f32, but scale applied in the stream dtype: keeps the
    # full-tensor f32 copy out of the HLO (XLA hoists convert(saved_stack)
    # out of the backward while-loop otherwise — 2× activation memory).
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * p["scale"].astype(x.dtype)


# ---------------------------------------------------------------- rope


def rope(x, positions, theta: float, inv_freq=None):
    """x: (..., S, H, hd); positions: (..., S) int32.  Half-split rotation;
    ``inv_freq`` (hd/2,) replaces ``theta ** (-2i/hd)`` (YaRN)."""
    hd = x.shape[-1]
    half = hd // 2
    if inv_freq is None:
        freqs = jnp.exp(-jnp.log(theta) *
                        jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]   # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, yarn) -> np.ndarray:
    """YaRN's inverse frequencies (DeepSeek-V2's form): a linear ramp over
    the rotation dims between the correction dims of ``beta_fast`` and
    ``beta_slow`` blends ``base ** (-2i/dim)`` (extrapolated, fast dims) with
    that value divided by ``factor`` (interpolated, slow dims)."""
    def corr(rot):
        return (dim * math.log(yarn.original_max_position
                               / (rot * 2 * math.pi))) / (2 * math.log(base))
    lo = max(math.floor(corr(yarn.beta_fast)), 0)
    hi = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - lo) / (hi - lo),
                   0.0, 1.0)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    return (extra / yarn.factor * ramp + extra * (1.0 - ramp)
            ).astype(np.float32)


# ---------------------------------------------------------------- attention


@dataclasses.dataclass(frozen=True)
class AttnCfg:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    window: Optional[int] = None        # sliding-window size (None = full)
    rope_theta: float = 1e4
    q_chunk: int = 2048                 # chunk queries above this seq len


def attn_init(key, c: AttnCfg, dtype=jnp.bfloat16):
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, kvh, hd = c.d_model, c.n_heads, c.n_kv_heads, c.head_dim
    s = d ** -0.5
    p = {
        "wq": (jax.random.normal(kq, (d, h * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(kk, (d, kvh * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(kv, (d, kvh * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ko, (h * hd, d)) * (h * hd) ** -0.5
               ).astype(dtype),
    }
    if c.qk_norm:
        p["q_norm"] = rmsnorm_init(hd)
        p["k_norm"] = rmsnorm_init(hd)
    return p


def _attend(q, k, v, *, causal_offset, window, scale):
    """q: (B,Sq,H,hd) k: (B,Sk,KV,hd) v: (B,Sk,KV,hv). causal_offset = abs
    pos of q[0] - abs pos of k[0] (so query i attends keys j with j <= i +
    causal_offset).
    causal_offset may be a (B,) vector — per-row offsets for continuous
    batching, where each batch slot sits at its own decode position."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    rep = H // KV
    qh = q.reshape(B, Sq, KV, rep, hd)
    # the scale rides on the queries: XLA folds a scale of the scores into
    # the dot in some programs and not in others (a scan body against the
    # same body unrolled), which breaks the split forwards' bitwise contract
    scores = jnp.einsum("bqkrh,bskh->bkrqs", qh * scale, k).astype(
        jnp.float32)
    co = jnp.asarray(causal_offset)
    kj = jnp.arange(k.shape[1])[None, :]
    if co.ndim == 1:
        qi = jnp.arange(Sq)[None, :, None] + co[:, None, None]  # (B,Sq,1)
        mask = kj[None] <= qi
        if window is not None:
            mask &= kj[None] > qi - window
        scores = jnp.where(mask[:, None, None], scores, -1e30)
    else:
        qi = jnp.arange(Sq)[:, None] + causal_offset
        mask = kj <= qi
        if window is not None:
            mask &= kj > qi - window
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrqs,bskv->bqkrv", probs, v)
    return out.reshape(B, Sq, H, v.shape[-1])


def attention(p, c: AttnCfg, x, positions, *, kv_cache=None, cache_len=None):
    """Self-attention.  Training/prefill: kv_cache None -> causal over x.
    Decode: kv_cache=(K,V) (B,Smax,KV,hd) updated at cache_len (static-shape
    dynamic_update_slice); returns (out, new_cache).  ``cache_len`` may be a
    (B,) vector — continuous-batching decode, where every slot writes and
    attends at its own offset (per-row scatter + per-row causal mask)."""
    B, S, d = x.shape
    h, kvh, hd = c.n_heads, c.n_kv_heads, c.head_dim
    q = (x @ p["wq"]).reshape(B, S, h, hd)
    k = (x @ p["wk"]).reshape(B, S, kvh, hd)
    v = (x @ p["wv"]).reshape(B, S, kvh, hd)
    if c.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    q = rope(q, positions, c.rope_theta)
    k = rope(k, positions, c.rope_theta)
    scale = hd ** -0.5

    if kv_cache is not None:
        K, V = kv_cache
        cl = jnp.asarray(cache_len)
        if cl.ndim == 1:
            b = jnp.arange(B)[:, None]
            pos = cl[:, None] + jnp.arange(S)[None, :]       # (B, S)
            K = K.at[b, pos].set(k.astype(K.dtype))
            V = V.at[b, pos].set(v.astype(V.dtype))
            kj = jnp.arange(K.shape[1])
            valid = kj[None, :] < (cl + S)[:, None]          # (B, Sk)
            out = _attend(q, jnp.where(valid[:, :, None, None], K, 0),
                          jnp.where(valid[:, :, None, None], V, 0),
                          causal_offset=cl, window=c.window, scale=scale)
            return (out.reshape(B, S, h * hd) @ p["wo"]), (K, V)
        K = jax.lax.dynamic_update_slice(K, k.astype(K.dtype), (0, cache_len, 0, 0))
        V = jax.lax.dynamic_update_slice(V, v.astype(V.dtype), (0, cache_len, 0, 0))
        # mask out cache positions beyond cache_len + S
        kj = jnp.arange(K.shape[1])
        valid = kj < cache_len + S
        out = _attend(q, jnp.where(valid[None, :, None, None], K, 0),
                      jnp.where(valid[None, :, None, None], V, 0),
                      causal_offset=cache_len, window=c.window, scale=scale)
        # invalid keys masked via causal_offset anyway (kj <= i + cache_len)
        out = out.reshape(B, S, h * hd)
        return (out @ p["wo"]), (K, V)

    out = _chunked_causal(q, k, v, c.q_chunk, scale, c.window)
    out = out.reshape(B, S, h * hd)
    return out @ p["wo"], None


def _chunked_causal(q, k, v, q_chunk: int, scale: float, window=None):
    """Causal attention of a whole sequence, queries in chunks of
    ``q_chunk`` (one ``lax.map`` step each) above that length, so no
    (S, S) score tensor is held at once."""
    B, S, h = q.shape[:3]
    if S <= q_chunk:
        return _attend(q, k, v, causal_offset=0, window=window, scale=scale)
    assert S % q_chunk == 0, (S, q_chunk)
    nch = S // q_chunk
    qs = q.reshape((B, nch, q_chunk) + q.shape[2:])

    def chunk(i, q_i):
        return _attend(q_i, k, v, causal_offset=i * q_chunk, window=window,
                       scale=scale)
    out = jax.lax.map(lambda args: chunk(*args),
                      (jnp.arange(nch), qs.swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape((B, S) + out.shape[3:])


# ---------------------------------------------------------------- MLA


@dataclasses.dataclass(frozen=True)
class MLACfg:
    """Multi-head latent attention without query compression (DeepSeek-V2
    §2.1, ``q_lora_rank`` null)."""
    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 1e4
    yarn: Optional[object] = None       # configs.base.Yarn
    q_chunk: int = 256                  # chunk queries above this seq len

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        s = self.qk_head_dim ** -0.5
        if self.yarn is not None and self.yarn.mscale_all_dim:
            s *= yarn_mscale(self.yarn.factor, self.yarn.mscale_all_dim) ** 2
        return s

    def inv_freq(self):
        if self.yarn is None:
            return None
        return yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta,
                             self.yarn)


def mla_init(key, c: MLACfg, dtype=jnp.bfloat16):
    kq, kd, ku, ko = jax.random.split(key, 4)
    d, h = c.d_model, c.n_heads
    r = c.kv_lora_rank
    return {
        "wq": (jax.random.normal(kq, (d, h * c.qk_head_dim)) * d ** -0.5
               ).astype(dtype),
        "w_dkv": (jax.random.normal(kd, (d, r + c.qk_rope_head_dim))
                  * d ** -0.5).astype(dtype),
        "kv_norm": rmsnorm_init(r),
        "w_ukv": (jax.random.normal(
            ku, (r, h * (c.qk_nope_head_dim + c.v_head_dim))) * r ** -0.5
            ).astype(dtype),
        "wo": (jax.random.normal(ko, (h * c.v_head_dim, d))
               * (h * c.v_head_dim) ** -0.5).astype(dtype),
    }


def mla_attention(p, c: MLACfg, x, positions):
    """Causal latent attention over the whole sequence (no cache): queries
    ``x·W_q`` split into nope and rope parts; ``[c_kv; k_pe] = x·W_dkv``,
    ``c_kv`` normed and expanded to per-head ``[k_nope; v]`` by ``W_ukv``;
    ``k_pe`` rotated once and shared by every head."""
    with jax.named_scope("mla"):
        B, S, _ = x.shape
        h, dn, dr = c.n_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
        inv = c.inv_freq()
        q = (x @ p["wq"]).reshape(B, S, h, dn + dr)
        q_pe = rope(q[..., dn:], positions, c.rope_theta, inv)
        q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
        ckv = x @ p["w_dkv"]
        k_pe = rope(ckv[..., None, c.kv_lora_rank:], positions,
                    c.rope_theta, inv)                    # (B, S, 1, dr)
        kv = (rmsnorm(p["kv_norm"], ckv[..., :c.kv_lora_rank]) @ p["w_ukv"]
              ).reshape(B, S, h, dn + c.v_head_dim)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (B, S, h, dr))], axis=-1)
        out = _chunked_causal(q, k, kv[..., dn:], c.q_chunk,
                              c.softmax_scale)
        return out.reshape(B, S, h * c.v_head_dim) @ p["wo"]


# ---------------------------------------------------------------- gated FFN


def ffn_init(key, d, f, *, gated=True, dtype=jnp.bfloat16):
    k1, k2, k3 = jax.random.split(key, 3)
    s = d ** -0.5
    p = {"w_up": (jax.random.normal(k1, (d, f)) * s).astype(dtype),
         "w_down": (jax.random.normal(k2, (f, d)) * f ** -0.5).astype(dtype)}
    if gated:
        p["w_gate"] = (jax.random.normal(k3, (d, f)) * s).astype(dtype)
    return p


def ffn(p, x, mask, site: linearize.MaskSite, *, poly=None, soft=False):
    """Gated (SwiGLU-style) or plain FFN with the *masked* activation.

    Masked semantics: act(h) at kept channels, identity (or poly2) at
    linearized channels; for gated FFNs the gate branch activation is the
    mask site (matching DESIGN §4).
    """
    h = x @ (p["w_gate"] if "w_gate" in p else p["w_up"])
    mode = linearize.fused_route_mode()
    if mode is not None and not soft and poly is None:
        # Suffix-engine tracing: gate [· up-branch] · w_down as one Pallas
        # megakernel — the gated (B, S, F) tensor never round-trips HBM
        # between the mask select and the down-projection.
        from repro.kernels import ops
        interpret = mode == "interpret"
        if interpret or ops.fused_dispatch_enabled():
            mul = (x @ p["w_up"]) if "w_gate" in p else None
            return ops.masked_act_matmul_routed(
                h, mask, p["w_down"], mul, kind=site.kind,
                interpret=interpret)
    a = linearize.apply_masked_act(h, mask, site, poly=poly, soft=soft)
    if "w_gate" in p:
        a = a * (x @ p["w_up"])
    return a @ p["w_down"]
