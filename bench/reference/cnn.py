"""Plain reference of a CIFAR-style ResNet with per-pixel ReLU masks.

Straightforward ``jax.numpy`` in float32 (convolutions at the matmul
precision that the configuration names for the reference), written from
the architecture's description (CIFAR form: 3x3 stem, basic blocks, 1x1
projection shortcuts, global average pool, linear classifier) and the
paper's masked activation
``m * relu(x) + (1 - m) * x``.  Batch norm uses the statistics of the batch
(the configuration states it).  No kernels, no caches, no batching of
candidates: one network, one mask tree, one batch at a time.

It also makes what the benchmark feeds the program: the weights and the
data, from a seed, on the device.  ``dtype=jnp.bfloat16`` computes the same
mathematics in bfloat16 (weights, activations and optimizer state): the
control that a correct comparison must reject.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: the matmul precision of float32 convolutions, by the configuration's
#: ``reference_precision``
PRECISION = {"highest": HIGHEST, "default": jax.lax.Precision.DEFAULT}


def _plan(cfg: dict):
    """(stage, block, cin, cout, stride, hw_out) for every basic block."""
    hw, cin = cfg["image_size"], cfg["stem_channels"]
    for si, (cout, n, stride) in enumerate(cfg["stages"]):
        for bi in range(n):
            s = stride if bi == 0 else 1
            hw //= s
            yield si, bi, cin, cout, s, hw
            cin = cout


def site_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    """Mask site -> (H, W, C), in forward order."""
    hw = cfg["image_size"]
    out = {"stem.relu": (hw, hw, cfg["stem_channels"])}
    for si, bi, _, cout, _, h in _plan(cfg):
        out[f"g{si}b{bi}.relu1"] = (h, h, cout)
        out[f"g{si}b{bi}.relu2"] = (h, h, cout)
    return out


def site_segment(cfg: dict) -> Dict[str, str]:
    """Mask site -> the segment (stem or block) that applies it."""
    return {s: s.split(".")[0] if not s.startswith("stem") else "stem"
            for s in site_shapes(cfg)}


# ------------------------------------------------------------- weights, data


def init_convs(cfg: dict, key) -> dict:
    """He-normal convolutions, unit batch norms and a zero classifier, in
    the parameter layout of the program's CNN."""
    def conv(k, kh, cin, cout):
        return jax.random.normal(k, (kh, kh, cin, cout), F32) \
            * (2.0 / (kh * kh * cin)) ** 0.5

    def bn(c):
        return {"scale": jnp.ones((c,), F32), "bias": jnp.zeros((c,), F32)}

    c0 = cfg["stem_channels"]
    p = {"stem": {"conv": conv(jax.random.fold_in(key, 0), 3, 3, c0),
                  "bn": bn(c0)}}
    for si, bi, cin, cout, s, _ in _plan(cfg):
        k = jax.random.fold_in(key, 100 + 10 * si + bi)
        blk = {"conv1": conv(jax.random.fold_in(k, 1), 3, cin, cout),
               "bn1": bn(cout),
               "conv2": conv(jax.random.fold_in(k, 2), 3, cout, cout),
               "bn2": bn(cout)}
        if s != 1 or cin != cout:
            blk["proj"] = conv(jax.random.fold_in(k, 3), 1, cin, cout)
        p[f"g{si}b{bi}"] = blk
    cf = cfg["stages"][-1][0]
    p["fc"] = {"w": jnp.zeros((cf, cfg["n_classes"]), F32),
               "b": jnp.zeros((cfg["n_classes"],), F32)}
    return p


def make_data(cfg: dict, key, n: int):
    """``n`` class-conditional images: a smooth per-class pattern (three
    plane waves per channel) plus Gaussian noise; labels uniform."""
    s, n_cls = cfg["image_size"], cfg["n_classes"]
    kf, kp, ka, kl, kn = jax.random.split(key, 5)
    freq = jax.random.uniform(kf, (n_cls, 3, 3, 2), F32, 1.0, 4.0)
    phase = jax.random.uniform(kp, (n_cls, 3, 3), F32, 0.0, 2 * np.pi)
    amp = jax.random.normal(ka, (n_cls, 3, 3), F32)
    g = jnp.linspace(0.0, 1.0, s)
    xx, yy = jnp.meshgrid(g, g)
    arg = 2 * np.pi * (freq[..., 0, None, None] * xx
                       + freq[..., 1, None, None] * yy) + phase[..., None,
                                                                None]
    pats = jnp.sum(amp[..., None, None] * jnp.sin(arg), axis=2)  # (C,3,s,s)
    pats = jnp.moveaxis(pats, 1, -1)
    labels = jax.random.randint(kl, (n,), 0, n_cls)
    noise = jax.random.normal(kn, (n, s, s, 3), F32) * cfg["noise"]
    return pats[labels] + noise, labels.astype(jnp.int32)


# ------------------------------------------------------------------ forward


def _conv(x, w, stride, precision):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _bn(p, x, eps=1e-5):
    mean = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.var(x, axis=(0, 1, 2), keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"].astype(x.dtype) \
        + p["bias"].astype(x.dtype)


def _act(x, m):
    m = m.astype(x.dtype)
    return m * jnp.maximum(x, 0) + (1 - m) * x


class Net:
    """The reference network of one configuration; jitted entry points
    take the compute dtype as a static argument.  float32 convolutions run
    at ``precision`` (default: the configuration's
    ``reference_precision``), others at the default."""

    def __init__(self, cfg: dict, precision: Optional[str] = None):
        self.cfg = cfg
        self.f32_precision = PRECISION[
            precision or cfg.get("reference_precision", "highest")]
        self.blocks = [(f"g{si}b{bi}", s) for si, bi, _, _, s, _ in
                       _plan(cfg)]
        self.correct = jax.jit(self.correct_count, static_argnames="dtype")
        self.sgd_step = jax.jit(self._sgd_step, static_argnames="dtype")

    def _precision(self, dtype):
        return self.f32_precision if dtype == F32 \
            else jax.lax.Precision.DEFAULT

    def features(self, params, masks, images, dtype=F32):
        """Pooled features (B, C) of the last stage."""
        prec = self._precision(dtype)
        x = images.astype(dtype)
        p = params["stem"]
        x = _act(_bn(p["bn"], _conv(x, p["conv"], 1, prec)),
                 masks["stem.relu"])
        for name, s in self.blocks:
            blk = params[name]
            y = _conv(x, blk["conv1"], s, prec)
            y = _act(_bn(blk["bn1"], y), masks[f"{name}.relu1"])
            y = _bn(blk["bn2"], _conv(y, blk["conv2"], 1, prec))
            sc = _conv(x, blk["proj"], s, prec) if "proj" in blk else x
            x = _act(y + sc, masks[f"{name}.relu2"])
        return jnp.mean(x, axis=(1, 2))

    def logits(self, params, masks, images, dtype=F32):
        f = self.features(params, masks, images, dtype)
        return jnp.dot(f, params["fc"]["w"].astype(dtype),
                       precision=self._precision(dtype)) \
            + params["fc"]["b"].astype(dtype)

    def correct_count(self, params, masks, images, labels, dtype=F32):
        """Number of images whose top logit is their label."""
        lg = self.logits(params, masks, images, dtype)
        return jnp.sum(jnp.argmax(lg, -1) == labels)

    def loss(self, params, masks, images, labels, dtype=F32):
        """Mean cross entropy."""
        lg = self.logits(params, masks, images, dtype).astype(F32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - gold)

    def _sgd_step(self, params, mu, masks, images, labels, lr_t, dtype):
        g = jax.grad(self.loss)(params, masks, images, labels, dtype)
        mu = jax.tree.map(lambda m, gi: (0.9 * m + gi).astype(dtype), mu, g)
        params = jax.tree.map(lambda p, m: (p - lr_t.astype(dtype) * m
                                            ).astype(dtype), params, mu)
        return params, mu, g

    def finetune(self, params, masks, batches: List[tuple], lr: float,
                 dtype=F32):
        """SGD with momentum 0.9 and a cosine schedule from ``lr`` to 0
        over ``len(batches)`` steps, under fixed masks.  Returns the
        parameters after the last step and the gradient of the first."""
        steps = len(batches)
        params = _cast(params, dtype)
        mu = jax.tree.map(jnp.zeros_like, params)
        masks = _cast(masks, dtype)
        first = None
        for i, (images, labels) in enumerate(batches):
            lr_t = jnp.asarray(0.5 * lr * (1 + np.cos(np.pi * i / steps)),
                               F32)
            params, mu, g = self.sgd_step(params, mu, masks, images, labels,
                                          lr_t, dtype=dtype)
            if first is None:
                first = g
        return _cast(params, F32), _cast(first, F32)

    def fit_readout(self, params, masks, images, labels):
        """Nearest-class-mean classifier on the pooled features of
        ``images`` under ``masks`` (logits ``-|f - mu_c|^2 / 2`` up to a
        per-image constant), scaled so that the logits' spread over images
        and classes is 2.  Fitted on images outside the eval batch, it
        leaves the eval batch's accuracy well inside (0, 100%), where a
        candidate's edit moves it; the scale keeps the finetune's gradients
        tame."""
        feats = self.features(params, masks, images)
        onehot = jax.nn.one_hot(labels, self.cfg["n_classes"], dtype=F32)
        mu = (onehot.T @ feats) / jnp.maximum(onehot.sum(0), 1.0)[:, None]
        w, b = mu.T, -0.5 * jnp.sum(mu * mu, -1)
        s = jnp.std(jnp.dot(feats, w, precision=HIGHEST) + b) / 2.0
        return {**params, "fc": {"w": w / s, "b": b / s}}


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)
