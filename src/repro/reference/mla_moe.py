"""Plain reference of DeepSeek-V2's block: latent attention (MLA) with YaRN
rope, a softmax top-k router over every routed expert, the experts held
here, shared experts, and per-channel masks on the SiLU gates.

Straightforward ``jax.numpy`` in float32 (matmuls at the precision the
caller names: ``"highest"`` in the tests, the configuration's own in the
benchmark), written from DeepSeek-V2 (arXiv:2405.04434) §2.1–2.2 and the
published ``config.json``; it imports nothing of the program.  One network,
one mask tree, one batch at a time: no kernels, no caches, no batching of
candidates, no scan over layers, every held expert computed for every token
and weighted by its gate (zero where the token did not pick it).

The configuration is a dict of the published ``config.json`` keys.  A
chip's share of an expert-parallel deployment holds ``n_routed_experts``
experts starting at ``expert_offset`` of the ``router_experts`` that the
router scores; what the experts held elsewhere add is left out.
``vocab_size`` is the slice of the vocabulary held here.

Departures from the published model, each deliberate:

* rope rotates the two halves of the 64 rope dimensions (``[x1; x2]``), not
  interleaved pairs as the published modeling code does; with weights from
  a seed that is the same model up to a fixed permutation of the rope
  columns of ``W_q`` and ``W_dkv``;
* the masked gate activation ``m·silu(h) + (1−m)·h`` (linearization) in
  every SiLU: the dense FFN, each held expert and the shared experts;
* no decode cache, no query compression (V2-Lite has none), no dropout.

It also makes what the benchmark feeds the program: weights in the
program's parameter layout and Markov token sequences, from a seed, on the
device.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
PRECISION = {"highest": jax.lax.Precision.HIGHEST,
             "default": jax.lax.Precision.DEFAULT}


# ------------------------------------------------------------------- shapes


def n_moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def router_experts(cfg: dict) -> int:
    return cfg.get("router_experts", cfg["n_routed_experts"])


def site_shapes(cfg: dict) -> Dict[str, tuple]:
    """Mask site -> shape, in the program's naming and forward order: the
    dense layers ``h<i>.ffn``, then per-layer stacks of the held experts'
    channels ``s0.moe`` and the shared experts' ``s0.moe_shared``."""
    L = n_moe_layers(cfg)
    out = {f"h{i}.ffn": (cfg["intermediate_size"],)
           for i in range(cfg["first_k_dense_replace"])}
    out["s0.moe"] = (L, cfg["n_routed_experts"],
                     cfg["moe_intermediate_size"])
    out["s0.moe_shared"] = (L, cfg["moe_intermediate_size"]
                            * cfg["n_shared_experts"])
    return out


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """Inverse frequencies of the rope dimensions: ``theta^(-2i/dim)``,
    blended by YaRN's linear ramp with the same divided by ``factor``."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    extra = base ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    y = cfg.get("rope_scaling")
    if not y:
        return extra.astype(np.float32)

    def corr_dim(rotations):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    lo = max(math.floor(corr_dim(y["beta_fast"])), 0)
    hi = min(math.ceil(corr_dim(y["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    inter = extra / y["factor"]
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    y = cfg.get("rope_scaling")
    if y and y.get("mscale_all_dim"):
        m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
        s *= m * m
    return s


# ------------------------------------------------------------ weights, data


def init_params(cfg: dict, key) -> dict:
    """Weights from a seed in the program's parameter layout: every matrix
    normal with variance 1/fan-in, norms at one, the router over every
    routed expert, the held experts only."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, V = cfg["kv_lora_rank"], cfg["vocab_size"]
    fe, Eh = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    fs = fe * cfg["n_shared_experts"]
    counter = iter(range(1 << 20))

    def w(*shape):
        k = jax.random.fold_in(key, next(counter))
        return jax.random.normal(k, shape, F32) * shape[-2] ** -0.5

    def ones(n):
        return {"scale": jnp.ones((n,), F32)}

    def attn():
        return {"wq": w(d, H * (dn + dr)), "w_dkv": w(d, r + dr),
                "kv_norm": ones(r), "w_ukv": w(r, H * (dn + dv)),
                "wo": w(H * dv, d)}

    def ffn(f):
        return {"w_gate": w(d, f), "w_up": w(d, f), "w_down": w(f, d)}

    head = [{"ln1": ones(d), "attn": attn(), "ln2": ones(d),
             "ffn": ffn(cfg["intermediate_size"])}
            for _ in range(cfg["first_k_dense_replace"])]
    layers = [{"ln1": ones(d), "attn": attn(), "ln2": ones(d),
               "moe": {"router": w(d, router_experts(cfg)),
                       "w_gate": w(Eh, d, fe), "w_up": w(Eh, d, fe),
                       "w_down": w(Eh, fe, d), "shared": ffn(fs)}}
              for _ in range(n_moe_layers(cfg))]
    params = {"embed": jax.random.normal(jax.random.fold_in(key, 1 << 21),
                                         (V, d), F32),
              "final_norm": ones(d), "head": head, "tail": [],
              "stack": {"0": jax.tree.map(lambda *a: jnp.stack(a),
                                          *layers)}}
    if not cfg["tie_word_embeddings"]:
        params["lm_head"] = w(d, V)
    return params


def make_tokens(cfg: dict, key, n: int, length: int, fanout: int = 8):
    """``n`` sequences of ``length`` ids from a Markov chain over the
    vocabulary slice: each id has ``fanout`` random successors with random
    probabilities; starts uniform."""
    V = cfg["vocab_size"]
    ks, kl, k0, kw = jax.random.split(key, 4)
    succ = jax.random.randint(ks, (V, fanout), 0, V)
    logits = jax.random.normal(kl, (V, fanout), F32) * 1.5
    first = jax.random.randint(k0, (n,), 0, V)

    def step(cur, k):
        j = jax.random.categorical(k, logits[cur])
        nxt = succ[cur, j]
        return nxt, nxt
    _, rest = jax.lax.scan(step, first, jax.random.split(kw, length - 1))
    return jnp.concatenate([first[:, None], rest.T], axis=1).astype(
        jnp.int32)


# ------------------------------------------------------------------ forward


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a,
                        tree)


class Net:
    """The reference network of one configuration; jitted entry points take
    the compute dtype as a static argument.  float32 matmuls run at
    ``precision`` (``"highest"`` or ``"default"``), others at the
    default."""

    def __init__(self, cfg: dict, precision: str = "highest"):
        self.cfg = cfg
        self.f32_precision = PRECISION[precision]
        self.inv_freq = yarn_inv_freq(cfg)
        self.scale = softmax_scale(cfg)
        self.logits_jit = jax.jit(self.logits, static_argnames="dtype")
        self.correct = jax.jit(self.correct_count, static_argnames="dtype")
        self.sgd_step = jax.jit(self._sgd_step, static_argnames="dtype")
        self.rows = jax.jit(self.held_rows)

    def _prec(self, dtype):
        return self.f32_precision if dtype == F32 \
            else jax.lax.Precision.DEFAULT

    def _dot(self, a, b, dtype):
        return jnp.dot(a, b.astype(a.dtype), precision=self._prec(dtype))

    def _rms(self, p, x):
        x32 = x.astype(F32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.cfg["rms_norm_eps"])
        return (y * p["scale"]).astype(x.dtype)

    def _rope(self, x, pos):
        half = x.shape[-1] // 2
        ang = pos[:, None].astype(F32) * jnp.asarray(self.inv_freq)
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(x.dtype)

    def _mla(self, p, x, dtype):
        c = self.cfg
        B, S, _ = x.shape
        H = c["num_attention_heads"]
        dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], \
            c["v_head_dim"]
        r = c["kv_lora_rank"]
        pos = jnp.arange(S)
        q = self._dot(x, p["wq"], dtype).reshape(B, S, H, dn + dr)
        q_nope, q_pe = q[..., :dn], self._rope(q[..., dn:], pos)
        ckv = self._dot(x, p["w_dkv"], dtype)
        k_pe = self._rope(ckv[..., None, r:], pos)           # (B, S, 1, dr)
        kv = self._dot(self._rms(p["kv_norm"], ckv[..., :r]), p["w_ukv"],
                       dtype).reshape(B, S, H, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        prec = self._prec(dtype)
        scale = jnp.asarray(self.scale, dtype)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope * scale, k_nope,
                             precision=prec)
                  + jnp.einsum("bqhd,bkd->bhqk", q_pe * scale, k_pe[:, :, 0],
                               precision=prec)).astype(F32)
        causal = pos[None, :] <= pos[:, None]
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=prec)
        return self._dot(out.reshape(B, S, H * dv), p["wo"], dtype)

    def _ffn(self, p, x, m, dtype):
        """SwiGLU with the masked SiLU on the gate branch."""
        h = self._dot(x, p["w_gate"], dtype)
        m = m.astype(dtype)
        a = m * jax.nn.silu(h) + (1 - m) * h
        return self._dot(a * self._dot(x, p["w_up"], dtype), p["w_down"],
                         dtype)

    def gates(self, p, x):
        """(T, router_experts) weights: each token's softmax score at its
        top-k experts (renormalized if ``norm_topk_prob``), 0 elsewhere."""
        c = self.cfg
        logits = jnp.dot(x.astype(F32), p["router"].astype(F32),
                         precision=self.f32_precision)
        probs = jax.nn.softmax(logits, axis=-1)
        top, idx = jax.lax.top_k(probs, c["num_experts_per_tok"])
        if c["norm_topk_prob"]:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        top = top * c.get("routed_scaling_factor", 1.0)
        g = jnp.zeros_like(probs)
        return jax.vmap(lambda gi, ii, ti: gi.at[ii].set(ti))(g, idx, top)

    def _moe(self, p, x, m, ms, dtype):
        c = self.cfg
        B, S, d = x.shape
        xt = x.reshape(B * S, d)
        e0 = c.get("expert_offset", 0)
        g = self.gates(p, xt)[:, e0:e0 + c["n_routed_experts"]]
        y = jnp.zeros_like(xt)
        for e in range(c["n_routed_experts"]):
            pe = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
            y = y + g[:, e:e + 1].astype(dtype) * self._ffn(pe, xt, m[e],
                                                             dtype)
        y = y + self._ffn(p["shared"], xt, ms, dtype)
        return y.reshape(B, S, d)

    def _layers(self, params, masks):
        """[(kind, layer params, masks)] in forward order."""
        out = [("dense", blk, masks[f"h{i}.ffn"])
               for i, blk in enumerate(params["head"])]
        stack = params["stack"]["0"]
        for i in range(n_moe_layers(self.cfg)):
            out.append(("moe", jax.tree.map(lambda a: a[i], stack),
                        (masks["s0.moe"][i], masks["s0.moe_shared"][i])))
        return out

    def logits(self, params, masks, tokens, dtype=F32):
        params, masks = _cast(params, dtype), _cast(masks, dtype)
        x = params["embed"][tokens]
        for kind, p, m in self._layers(params, masks):
            x = x + self._mla(p["attn"], self._rms(p["ln1"], x), dtype)
            h = self._rms(p["ln2"], x)
            x = x + (self._ffn(p["ffn"], h, m, dtype) if kind == "dense"
                     else self._moe(p["moe"], h, *m, dtype))
        head = params["embed"].T if self.cfg["tie_word_embeddings"] \
            else params["lm_head"]
        return self._dot(self._rms(params["final_norm"], x), head, dtype)

    def held_rows(self, params, masks, tokens):
        """(expert layers, held) rows routed to each held expert."""
        c = self.cfg
        e0, Eh = c.get("expert_offset", 0), c["n_routed_experts"]
        x = params["embed"][tokens]
        rows = []
        for kind, p, m in self._layers(params, masks):
            x = x + self._mla(p["attn"], self._rms(p["ln1"], x), F32)
            h = self._rms(p["ln2"], x)
            if kind == "moe":
                g = self.gates(p["moe"], h.reshape(-1, h.shape[-1]))
                rows.append(jnp.sum(g[:, e0:e0 + Eh] > 0, axis=0))
                x = x + self._moe(p["moe"], h, *m, F32)
            else:
                x = x + self._ffn(p["ffn"], h, m, F32)
        return jnp.stack(rows).astype(jnp.int32)

    def correct_count(self, params, masks, tokens, labels, dtype=F32):
        """Positions whose top logit is their label."""
        lg = self.logits(params, masks, tokens, dtype)
        return jnp.sum(jnp.argmax(lg, -1) == labels)

    def loss(self, params, masks, tokens, labels, dtype=F32):
        """Mean cross entropy against ``labels``."""
        lg = self.logits(params, masks, tokens, dtype).astype(F32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - gold)

    def _sgd_step(self, params, mu, masks, tokens, labels, lr_t, dtype):
        g = jax.grad(self.loss)(params, masks, tokens, labels, dtype)
        mu = jax.tree.map(lambda m, gi: (0.9 * m + gi).astype(dtype), mu, g)
        params = jax.tree.map(lambda p, m: (p - lr_t.astype(dtype) * m
                                            ).astype(dtype), params, mu)
        return params, mu, g

    def finetune(self, params, masks, batches: List[tuple], lr: float,
                 dtype=F32):
        """SGD with momentum 0.9 and a cosine schedule from ``lr`` to 0
        over ``len(batches)`` steps of ``(tokens, labels)``, under fixed
        masks.  Returns the parameters after the last step and the gradient
        of the first."""
        steps = len(batches)
        params = _cast(params, dtype)
        mu = jax.tree.map(jnp.zeros_like, params)
        first = None
        for i, (tokens, labels) in enumerate(batches):
            lr_t = jnp.asarray(0.5 * lr * (1 + np.cos(np.pi * i / steps)),
                               F32)
            params, mu, g = self.sgd_step(params, mu, masks, tokens, labels,
                                          lr_t, dtype=dtype)
            if first is None:
                first = g
        return _cast(params, F32), _cast(first, F32)
