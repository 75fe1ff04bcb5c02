"""Mixture-of-Experts FFN: sort-based dispatch, capacity-bounded or drop-free.

``dispatch='held'`` is the drop-free layer of an expert-parallel
deployment: the router scores all ``n_experts``, the layer holds experts
``[expert_offset, expert_offset + n_held)`` and computes their part of the
result for every (token, k) pair routed to them — sorted by expert, one
grouped matmul (``lax.ragged_dot``) per projection, no capacity, no token
dropped.  Under a vmap (the BCD engine's candidate axis) the layer folds
the vmapped axes into the sorted rows (``custom_vmap``), since
``ragged_dot`` has no batching rule but over its rows.

The capacity-bounded modes below ('scatter', 'gather') hold every expert.

Dispatch is done *per batch row* (vmap over B): top-k routing, argsort by
expert id, capacity clip, gather → (E, C, d) → batched expert einsums →
scatter-add back.  Because the sort runs over the (unsharded) sequence axis
and batch is the data-parallel axis, GSPMD keeps all dispatch local to each
data shard; expert weights are tensor-parallel over 'model' (d_ff split), so
no quadratic one-hot dispatch matmuls and no token all-to-alls — FLOPs stay
≈ top_k/E-proportional (MODEL_FLOPS ratio stays honest).

Covers mixtral (8e top-2) and deepseek-moe (2 shared + 64e top-6,
fine-grained d_ff).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import custom_batching

from repro.core import linearize
from . import layers


@dataclasses.dataclass(frozen=True)
class MoECfg:
    d_model: int
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0            # deepseek: always-on shared experts
    d_ff_shared: int = 0
    capacity_factor: float = 1.25
    # 'scatter': d-wide scatter dispatch (baseline; GSPMD replicates the
    #            scatter operands — see EXPERIMENTS.md §Perf).
    # 'gather':  d-wide ops are gathers only; scatters touch int32 index
    #            vectors (tiny).  GSPMD partitions gathers cleanly.
    dispatch: str = "scatter"
    norm_topk: bool = True       # renormalize the top-k gates to sum to 1
    n_held: int = 0              # 'held': experts held here (0 = all)
    expert_offset: int = 0       # 'held': first expert held here

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


def moe_init(key, c: MoECfg, dtype=jnp.bfloat16):
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    d, e, f = c.d_model, c.held, c.d_ff_expert
    s = d ** -0.5
    p = {
        "router": (jax.random.normal(kr, (d, c.n_experts)) * s
                   ).astype(jnp.float32),
        "w_gate": (jax.random.normal(kg, (e, d, f)) * s).astype(dtype),
        "w_up": (jax.random.normal(ku, (e, d, f)) * s).astype(dtype),
        "w_down": (jax.random.normal(kd, (e, f, d)) * f ** -0.5).astype(dtype),
    }
    if c.n_shared:
        p["shared"] = layers.ffn_init(ks, d, c.d_ff_shared, gated=True,
                                      dtype=dtype)
    return p


def _capacity(c: MoECfg, seq: int) -> int:
    cap = int(seq * c.top_k * c.capacity_factor / c.n_experts) + 1
    if seq == 1:        # decode: exact capacity — a token routes to at most
        return 1        # one slot per expert (§Perf: the rounded-up 8 slots
                        # per expert cost 8x dispatch traffic per step)
    return max(8, -(-cap // 8) * 8)  # round up to multiple of 8


def _dispatch_row(x, logits, c: MoECfg, C: int):
    """x: (S, d), logits: (S, E) -> gathered (E*C, d), slot bookkeeping.

    The bookkeeping is carried in UNSORTED per-(token, k) layout (sort
    inverted via the int32 scatter-of-a-permutation idiom from
    :func:`_route` — unique indices, order-independent) so
    :func:`_combine_row` is a fixed-order gather + top-k reduction with no
    duplicate-index scatter."""
    S = x.shape[0]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, eidx = jax.lax.top_k(probs, c.top_k)          # (S, k)
    if c.norm_topk:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    flat_e = eidx.reshape(-1)                            # (S*k,)
    flat_t = jnp.repeat(jnp.arange(S), c.top_k)
    order = jnp.argsort(flat_e)
    se, st = flat_e[order], flat_t[order]
    # position within expert along the sorted order
    onehot = jax.nn.one_hot(se, c.n_experts, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), se[:, None],
                              axis=1)[:, 0] - 1
    keep = pos < C
    slot = jnp.where(keep, se * C + pos, c.n_experts * C)  # overflow slot
    # duplicate indices occur only at the overflow slot, where every write
    # is zeros — kept slots are unique, so the .set is order-independent
    xg = jnp.zeros((c.n_experts * C + 1, x.shape[1]), x.dtype)
    xg = xg.at[slot].set(jnp.where(keep[:, None], x[st], 0))
    slot_tk = jnp.zeros((S * c.top_k,), jnp.int32).at[order].set(
        slot.astype(jnp.int32)).reshape(S, c.top_k)
    keep_tk = jnp.zeros((S * c.top_k,), bool).at[order].set(
        keep).reshape(S, c.top_k)
    return xg[:-1], (gates, slot_tk, keep_tk)


def _combine_row(y_slots, book, S, d):
    """Combine expert outputs back to tokens with a fixed-order top-k sum.

    Replaces the historical ``out.at[st].add(ys * w)`` scatter-add: ``st``
    held every token ``top_k`` times, and XLA's accumulation order over
    duplicate scatter indices is unspecified — so the same routing could
    combine in different orders under the per-row vmap vs the
    candidate-stacked (double-vmapped) lowering, breaking the engine's
    bitwise stacked-vs-sequential contract when capacity overflow drops
    tokens.  Gathering per (token, k) and reducing over the k axis is a
    plain fixed-association sum — identical however it is batched, and
    matching ``batched_gather``'s einsum combine."""
    gates, slot_tk, keep_tk = book
    k = slot_tk.shape[1]
    ypad = jnp.concatenate([y_slots, jnp.zeros((1, d), y_slots.dtype)],
                           axis=0)
    ytk = ypad[slot_tk.reshape(-1)].reshape(S, k, d)
    w = gates.astype(ytk.dtype) * keep_tk.astype(ytk.dtype)
    return jnp.einsum("skd,sk->sd", ytk, w)


def _route(logits, c: MoECfg, C: int):
    """Shared routing bookkeeping — only small int/float vectors, no d-wide
    tensors.  Returns per-(token,k) slot ids and per-slot source tokens."""
    S = logits.shape[0]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, eidx = jax.lax.top_k(probs, c.top_k)              # (S, k)
    if c.norm_topk:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    flat_e = eidx.reshape(-1)                                # (S*k,)
    order = jnp.argsort(flat_e)
    se = flat_e[order]
    st = jnp.repeat(jnp.arange(S), c.top_k)[order]
    onehot = jax.nn.one_hot(se, c.n_experts, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), se[:, None],
                              axis=1)[:, 0] - 1
    keep = pos < C
    slot_sorted = jnp.where(keep, se * C + pos, c.n_experts * C)
    # per-slot source token (int32 scatter over E*C+1 — tiny)
    slot_src = jnp.full((c.n_experts * C + 1,), S, jnp.int32)
    slot_src = slot_src.at[slot_sorted].set(st.astype(jnp.int32))
    # per-(token,k) slot id, unsorted (int32 scatter over S*k — tiny)
    inv = jnp.zeros((S * c.top_k,), jnp.int32).at[order].set(
        slot_sorted.astype(jnp.int32))
    slot_tk = inv.reshape(S, c.top_k)
    return gates, slot_src, slot_tk


# ------------------------------------------------------- drop-free, held


def route(router, c: MoECfg, x):
    """Top-k expert ids and gates of every token over all ``n_experts``:
    softmax scores in float32, greedy top-k."""
    with jax.named_scope("moe.route"):
        probs = jax.nn.softmax(x.astype(jnp.float32) @ router, axis=-1)
        gates, eidx = jax.lax.top_k(probs, c.top_k)
        if c.norm_topk:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        return eidx, gates


def held_sizes(eidx, c: MoECfg):
    """Rows routed to each held expert: (n_held,) int32 counts of the
    (token, k) pairs whose expert is held here."""
    local = eidx.reshape(-1) - c.expert_offset
    return jnp.sum(local[:, None] == jnp.arange(c.held)[None, :], axis=0,
                   dtype=jnp.int32)


def _held_core(x, eidx, gates, grp, mask, poly, wg, wu, wd, *, e0, kind,
               replacement, soft):
    """x: (T, d) tokens; eidx, gates: (T, k); mask: (G, n_held, F) masks
    of ``G`` mask groups and ``grp``: (T,) each token's group (a folded
    candidate axis; one group when nothing is folded); poly: (3, G,
    n_held, F) or None.  Returns the held experts' gated sum (T, d)."""
    T, d = x.shape
    k = eidx.shape[1]
    Eh, _, F = wg.shape
    local = eidx.reshape(-1) - e0
    held = (local >= 0) & (local < Eh)
    key = jnp.where(held, local, Eh)              # pairs held elsewhere last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(Eh)[None, :], axis=0,
                    dtype=jnp.int32)
    tok = order // k
    # sorted rows past the held groups belong to no group: the TPU's
    # grouped matmul leaves them (and their gradients) unwritten, so every
    # operand and result is zeroed there
    live = (jnp.arange(T * k) < jnp.sum(sizes))[:, None]

    def grouped(lhs, w):
        return jnp.where(live, jax.lax.ragged_dot(lhs, w, sizes), 0)
    with jax.named_scope("moe.experts"):
        xs = jnp.where(live, x[tok], 0)
        midx = grp[tok] * Eh + jnp.minimum(key[order], Eh - 1)
        m = mask.reshape(-1, F)[midx]
        pr = None if poly is None else poly.reshape(3, -1, F)[:, midx]
        a = linearize.masked_act_plain(
            grouped(xs, wg), m, linearize.MaskSite((F,), kind, replacement),
            poly=pr, soft=soft)
        ys = grouped(a * grouped(xs, wu), wd)     # (T*k, d), sorted
    yk = ys[jnp.argsort(order)].reshape(T, k, d)
    # gates weigh the rows elementwise (an einsum would round them to the
    # matmul precision); rows held elsewhere are zeros
    w = jnp.where(held.reshape(T, k), gates, 0).astype(yk.dtype)
    return jnp.sum(yk * w[..., None], axis=1)


def _bcast(axis_size, batched, v):
    if v is None or batched:
        return v
    return jnp.broadcast_to(v[None], (axis_size,) + v.shape)


@functools.lru_cache(maxsize=None)
def _held_fn(e0: int, kind: str, replacement: str, soft: bool):
    core = functools.partial(_held_core, e0=e0, kind=kind,
                             replacement=replacement, soft=soft)

    @custom_batching.custom_vmap
    def f(x, eidx, gates, grp, mask, poly, wg, wu, wd):
        return core(x, eidx, gates, grp, mask, poly, wg, wu, wd)

    @f.def_vmap
    def _rule(axis_size, in_batched, x, eidx, gates, grp, mask, poly, wg,
              wu, wd):
        if any(in_batched[6:]):
            raise NotImplementedError(
                "held experts under a vmap of their weights")
        x, eidx, gates, grp, mask, poly = [
            _bcast(axis_size, b, v) for b, v in
            zip(in_batched, (x, eidx, gates, grp, mask, poly))]
        C, T = x.shape[:2]
        G = mask.shape[1]
        grp = (grp + G * jnp.arange(C, dtype=grp.dtype)[:, None])
        y = f(x.reshape(C * T, -1), eidx.reshape(C * T, -1),
              gates.reshape(C * T, -1), grp.reshape(-1),
              mask.reshape((C * G,) + mask.shape[2:]),
              None if poly is None else jnp.moveaxis(poly, 0, 1).reshape(
                  (3, C * G) + poly.shape[3:]),
              wg, wu, wd)
        return y.reshape(C, T, -1), True

    @jax.custom_vjp
    def g(x, eidx, gates, grp, mask, poly, wg, wu, wd):
        return f(x, eidx, gates, grp, mask, poly, wg, wu, wd)

    def g_fwd(*args):
        return jax.vjp(core, *args)

    def g_bwd(vjp, ct):
        return vjp(ct)

    g.defvjp(g_fwd, g_bwd)
    return g


def held_experts(p, c: MoECfg, x, eidx, gates, mask, site, *, poly=None,
                 soft=False):
    """The held experts' part of the layer for tokens ``x`` (..., d) routed
    as ``eidx``/``gates`` (..., k); ``mask`` (n_held, F) per-expert channel
    masks of the masked gate activation."""
    if not (soft or site.replacement == "poly2"):
        poly = None
    lead = x.shape[:-1]
    T = math.prod(lead)
    fn = _held_fn(c.expert_offset, site.kind, site.replacement, soft)
    y = fn(x.reshape(T, -1), eidx.reshape(T, -1), gates.reshape(T, -1),
           jnp.zeros((T,), jnp.int32), mask[None],
           None if poly is None else poly[:, None],
           p["w_gate"], p["w_up"], p["w_down"])
    return y.reshape(lead + (-1,))


def moe_ffn(p, c: MoECfg, x, mask, site: linearize.MaskSite,
            shared_mask=None, shared_site=None, *, poly=None,
            shared_poly=None, soft=False, act_spec=None):
    """x: (B, S, d).  mask: (E, F) per-expert channel masks.  shared_poly:
    poly2 coefficients for the shared-expert FFN gate (the ``moe_shared``
    site — distinct from the routed experts' ``poly``).  act_spec: the
    model's (B,S,D) PartitionSpec — its batch axes are re-asserted on the
    (B,E,C,·) expert tensors (GSPMD drops batch sharding through the
    dispatch gathers otherwise — §Perf, mixtral)."""
    B, S, d = x.shape
    if c.dispatch == "held":
        eidx, gates = route(p["router"], c, x)
        y = held_experts(p, c, x, eidx, gates, mask, site, poly=poly,
                         soft=soft)
        return (y + _shared(p, x, shared_mask, shared_site, shared_poly,
                            soft)).astype(x.dtype)
    C = _capacity(c, S)
    bspec = act_spec[0] if act_spec is not None else None

    def keep_batch(t, last=None):
        if act_spec is None:
            return t
        from jax.sharding import PartitionSpec as P
        return jax.lax.with_sharding_constraint(
            t, P(bspec, *([None] * (t.ndim - 2) + [last])))
    logits = x.astype(jnp.float32) @ p["router"]

    def experts(xe):
        h = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
        # masked activation per expert: flatten (E, C, F) with (E, F) mask
        a = linearize.apply_masked_act(
            h.transpose(1, 0, 2), mask, site, poly=poly, soft=soft
        ).transpose(1, 0, 2)
        a = a * jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
        return jnp.einsum("ecf,efd->ecd", a, p["w_down"])

    def row_scatter(xr, lr):
        xg, book = _dispatch_row(xr, lr, c, C)
        ye = experts(xg.reshape(c.n_experts, C, d))
        return _combine_row(ye.reshape(-1, d), book, S, d)

    def batched_gather(x, logits):
        """Batched (vmap-free) gather dispatch: d-wide ops are batched
        take_along_axis gathers, which GSPMD partitions along the batch axis
        without replication (a vmapped per-row gather does not — §Perf)."""
        gates, slot_src, slot_tk = jax.vmap(lambda lr: _route(lr, c, C))(
            logits)
        xpad = jnp.concatenate([x, jnp.zeros((B, 1, d), x.dtype)], axis=1)
        xe = jnp.take_along_axis(
            xpad, slot_src[:, :-1, None].astype(jnp.int32), axis=1)
        xe = keep_batch(xe.reshape(B, c.n_experts, C, d))
        h = keep_batch(jnp.einsum("becd,edf->becf", xe, p["w_gate"]),
                       "model")
        a = linearize.apply_masked_act(
            h.transpose(0, 2, 1, 3), mask, site, poly=poly, soft=soft
        ).transpose(0, 2, 1, 3)
        a = a * jnp.einsum("becd,edf->becf", xe, p["w_up"])
        ye = jnp.einsum("becf,efd->becd", a, p["w_down"]).reshape(B, -1, d)
        ye = keep_batch(ye)
        ypad = jnp.concatenate([ye, jnp.zeros((B, 1, d), ye.dtype)], axis=1)
        idx = jnp.minimum(slot_tk, c.n_experts * C).reshape(B, -1)
        ytk = jnp.take_along_axis(ypad, idx[..., None], axis=1)
        ytk = ytk.reshape(B, S, c.top_k, d)
        valid = (slot_tk < c.n_experts * C).astype(ytk.dtype)
        w = gates.astype(ytk.dtype) * valid
        return jnp.einsum("bskd,bsk->bsd", ytk, w)

    if c.dispatch == "gather":
        y = batched_gather(x, logits)
    else:
        y = jax.vmap(row_scatter)(x, logits)
    return (y + _shared(p, x, shared_mask, shared_site, shared_poly, soft)
            ).astype(x.dtype)


def _shared(p, x, mask, site, poly, soft):
    """The always-on shared experts (one FFN of their summed width)."""
    if "shared" not in p:
        return 0.0
    with jax.named_scope("moe.shared"):
        return layers.ffn(p["shared"], x, mask, site, poly=poly, soft=soft)
