"""Roofline analysis from compiled dry-run artifacts (no hardware needed).

Three terms per (arch × shape × mesh), in seconds:
  compute    = HLO_FLOPs_global / (chips × peak_FLOP/s)
  memory     = HLO_bytes_global / (chips × HBM_bw)
  collective = collective_bytes_global / (chips × link_bw)

``cost_analysis()`` yields per-device FLOPs/bytes of the partitioned module
(global = ×chips).  collective_bytes is parsed from the partitioned HLO text:
for each all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute we take the op's local tensor bytes, apply the standard
ring-model factor, and multiply by participants to get global bytes moved.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks."""
    flops: float                 # bf16 FLOP/s
    hbm_bw: float                # HBM bytes/s
    link_bw: float               # bytes/s per ICI link


# Keyed by ``jax.Device.device_kind``.  TPU v5e: Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of ICI per chip
# over 4 links (50 GB/s each).
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; a device not in :data:`PEAKS` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


def xla_cost(compiled) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a dict ({} when the backend offers no
    cost analysis)."""
    return dict(compiled.cost_analysis() or {})


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|(?P<ty>\w+)\[(?P<shape>[\d,]*)\][^ ]*)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_TUPLE_ELEM_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _bytes_of(ty: str, shape: str) -> int:
    n = 1
    for d in shape.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES.get(ty, 4)


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return default


@dataclasses.dataclass
class CollectiveStats:
    bytes_moved_global: float = 0.0
    counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    bytes_by_op: Dict[str, float] = dataclasses.field(default_factory=dict)
    in_loop_count: int = 0


_WHILE_BODY_RE = re.compile(r"body=%([\w.\-]+)")
_COMP_DEF_RE = re.compile(r"^([\w.\-]+)\s*[(]")


def _while_body_names(hlo_text: str) -> set:
    return set(_WHILE_BODY_RE.findall(hlo_text))


def parse_collectives(hlo_text: str, n_devices: int,
                      loop_trip_count: int = 1) -> CollectiveStats:
    """Sum collective bytes.  XLA cost/HLO text counts a while-loop body ONCE;
    collectives that live inside a while body (the layer scan, fwd and bwd)
    are multiplied by ``loop_trip_count`` (= n_repeats of the scanned stack).
    """
    bodies = _while_body_names(hlo_text)
    stats = CollectiveStats()
    current_comp = None
    for line in hlo_text.splitlines():
        ls = line.strip()
        if ls.startswith("%") and ("{" in ls) and ("->" in ls) \
                and not ls.startswith("%param"):
            m = _COMP_DEF_RE.match(ls.lstrip("%"))
            if m:
                current_comp = m.group(1)
        elif ls.startswith(("ENTRY", "HloModule")):
            current_comp = None
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        mult = loop_trip_count if (current_comp in bodies) else 1
        if mult > 1:
            stats.in_loop_count += 1
        if m.group("ty"):
            local = _bytes_of(m.group("ty"), m.group("shape"))
        else:  # tuple result: sum elements
            paren = line.split("=", 1)[1]
            local = sum(_bytes_of(t, s)
                        for t, s in _TUPLE_ELEM_RE.findall(
                            paren.split("(", 1)[0]))
        n = max(2, _group_size(line, n_devices))
        ring = (n - 1) / n
        if op == "all-reduce":
            moved = 2 * local * ring          # reduce-scatter + all-gather
        elif op == "collective-permute":
            moved = local
        else:                                  # ag / rs / a2a
            moved = local * ring
        stats.bytes_moved_global += moved * n * mult
        stats.counts[op] = stats.counts.get(op, 0) + 1
        stats.bytes_by_op[op] = stats.bytes_by_op.get(op, 0.0) \
            + moved * n * mult
    return stats


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    device_kind: str               # key into PEAKS
    flops_per_device: float        # raw XLA cost_analysis (body-once!)
    bytes_per_device: float        # raw XLA cost_analysis (body-once!)
    collective_bytes_global: float
    model_flops_global: float
    analytic_flops_global: float = 0.0   # loop-corrected (preferred)
    analytic_bytes_global: float = 0.0
    bytes_per_device_peak: Optional[float] = None   # memory_analysis

    @property
    def peaks(self) -> Peaks:
        return peaks(self.device_kind)

    @property
    def t_compute(self):
        if self.analytic_flops_global:
            return self.analytic_flops_global / (self.chips
                                                 * self.peaks.flops)
        return self.flops_per_device / self.peaks.flops

    @property
    def t_memory(self):
        if self.analytic_bytes_global:
            return self.analytic_bytes_global / (self.chips
                                                 * self.peaks.hbm_bw)
        return self.bytes_per_device / self.peaks.hbm_bw

    @property
    def t_collective(self):
        return self.collective_bytes_global / (self.chips
                                               * self.peaks.link_bw)

    @property
    def bottleneck(self):
        ts = {"compute": self.t_compute, "memory": self.t_memory,
              "collective": self.t_collective}
        return max(ts, key=ts.get)

    @property
    def useful_flops_ratio(self):
        return self.model_flops_global / max(self.hlo_flops_global, 1.0)

    @property
    def roofline_fraction(self):
        """Fraction of the hardware roof actually doing model math:
        (MODEL_FLOPS / chips / peak) / max(term) — 1.0 = perfect."""
        t_model = self.model_flops_global / (self.chips * self.peaks.flops)
        t_dom = max(self.t_compute, self.t_memory, self.t_collective)
        return t_model / max(t_dom, 1e-30)

    @property
    def hlo_flops_global(self):
        return self.analytic_flops_global or \
            self.flops_per_device * self.chips

    def row(self):
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops_global,
            "hlo_flops_global": self.hlo_flops_global,
            "xla_flops_global_raw": self.flops_per_device * self.chips,
            "xla_bytes_global_raw": self.bytes_per_device * self.chips,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def model_flops(cfg, shape, mode: str) -> float:
    """6·N_active·D (train: ×3 fwd+bwd via the standard 6ND; inference: 2ND)."""
    n_active = active_params(cfg)
    if mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch * 1
    return 2.0 * n_active * tokens


def block_fwd_flops(cfg, blk, new_tokens: float, ctx: float,
                    mode: str = "prefill"):
    """Analytic forward cost of ONE block: (flops, weight_bytes,
    decode_cache_bytes).

    The per-block term :func:`analytic_cell` sums over the whole stack;
    exposed separately so per-layer *fractions* (the suffix cost model's
    prefix_fraction — models' ``site_prefix_fractions``) share the same
    arithmetic.  ``new_tokens`` is batch×new positions, ``ctx`` the
    attention context length.
    """
    d = cfg.d_model
    hd, H, KV = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    k = blk.kind
    cache_bytes = 0.0
    if k in ("dense", "moe", "attn_only") and cfg.kv_lora_rank:
        # latent attention: q, [c_kv; k_pe], [k_nope; v], out projections;
        # scores over the qk head dims, values over v_head_dim
        r, dq = cfg.kv_lora_rank, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        dv = cfg.v_head_dim
        wq = d * H * dq + d * (r + cfg.qk_rope_head_dim) \
            + r * H * (cfg.qk_nope_head_dim + dv) + H * dv * d
        span = ctx if mode == "decode" else ctx / 2
        f = 2 * new_tokens * wq + 2 * new_tokens * H * (dq + dv) * span
        wb = wq * 2
    elif k in ("dense", "moe", "attn_only"):
        f_attn_proj = 2 * new_tokens * d * (H + 2 * KV) * hd \
            + 2 * new_tokens * H * hd * d
        kv_len = min(ctx, blk.window or ctx)
        if mode == "decode":
            f_sc = 2 * new_tokens * H * hd * kv_len * 2
        else:
            # causal: average key span ~ kv_len/2 (full) or window
            span = (ctx / 2) if blk.window is None else \
                min(blk.window, ctx / 2)
            f_sc = 2 * new_tokens * H * hd * span * 2
        f = f_attn_proj + f_sc
        wb = (d * (H + 2 * KV) * hd + H * hd * d) * 2
        if mode == "decode":
            cache_bytes += new_tokens * kv_len * KV * hd * 2 * 2
    if k in ("dense", "moe", "attn_only"):
        if k == "dense":
            nf = 3 if cfg.gated_ffn else 2
            f += 2 * new_tokens * d * cfg.d_ff * nf
            wb += d * cfg.d_ff * nf * 2
        elif k == "moe":
            held = cfg.moe_dispatch == "held"
            E = (cfg.n_experts_held or cfg.n_experts) if held \
                else cfg.n_experts
            # drop-free held experts: the pairs routed here (uniform
            # routing); capacity modes: every padded slot
            cap = cfg.top_k * E / cfg.n_experts if held \
                else cfg.top_k * cfg.capacity_factor
            f += 2 * new_tokens * d * cfg.n_experts          # router
            f += 2 * new_tokens * cap * 3 * d * cfg.d_ff_expert
            wb += 3 * E * d * cfg.d_ff_expert * 2
            if cfg.n_shared_experts:
                f += 2 * new_tokens * 3 * d * cfg.d_ff_shared
                wb += 3 * d * cfg.d_ff_shared * 2
    elif k == "mamba":
        di = cfg.d_inner
        nh = di // cfg.mamba_head_dim
        N, mh = cfg.ssm_state, cfg.mamba_head_dim
        chunk = 64 if mode != "decode" else 1
        f = 2 * new_tokens * d * 2 * di \
            + 2 * new_tokens * d * (2 * N + nh) \
            + 2 * new_tokens * di * d \
            + 4 * new_tokens * di  # conv
        # chunked SSD: scores (chunk·N) + y (chunk·mh) + state (2·N·mh)
        f += 2 * new_tokens * nh * (chunk * N + chunk * mh + 2 * N * mh)
        wb = (d * 2 * di + d * (2 * N + nh) + di * d) * 2
        if mode == "decode":
            cache_bytes += new_tokens * nh * N * mh * 4
    elif k == "rwkv":
        f_ff = cfg.d_ff
        rh = cfg.rwkv_head_dim
        Hr = d // rh
        chunk = 32 if mode != "decode" else 1
        f = 2 * new_tokens * d * d * 6 \
            + 2 * new_tokens * d * f_ff * 2 + 2 * new_tokens * d * d
        f += 2 * new_tokens * Hr * (chunk * rh * 2 + 2 * rh * rh)
        wb = (7 * d * d + 2 * d * f_ff) * 2
        if mode == "decode":
            cache_bytes += new_tokens * Hr * rh * rh * 4
    else:
        raise ValueError(k)
    return f, wb, cache_bytes


def moe_capacity_slots(cfg, seq: int) -> int:
    """Per-expert slot count of the sort-based MoE dispatch.

    Mirrors ``models.moe._capacity``: decode (seq == 1) is exact — one slot
    per expert — and everything else rounds up to a multiple of 8 with a
    floor of 8.  The expert einsums compute ALL ``E·C`` slots whether or
    not tokens fill them, so segment-level costing must use this padded
    figure, not the analytic ``top_k·capacity_factor`` per-token average.
    """
    if seq == 1:
        return 1
    cap = int(seq * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    return max(8, -(-cap // 8) * 8)


def lm_segment_fwd_flops(cfg, *, seq_len: int) -> list:
    """Per-segment forward FLOPs of the unified LM (per-sample, prefill):
    ``[embed, head…, stack repeat 0 … R-1, tail…, logits]``.

    The scanned stack contributes one entry PER REPEAT — the per-repeat
    prefix cuts in ``models.lm`` need per-repeat fractions, and every
    repeat runs the identical pattern so the entries are equal.  MoE
    blocks are corrected from :func:`block_fwd_flops`'s analytic
    ``top_k·capacity_factor`` average to the dispatch's true padded slot
    capacity (:func:`moe_capacity_slots`): the expert einsums pay for
    every ``E·C`` slot, filled or not.
    """
    def f(blk):
        fl = block_fwd_flops(cfg, blk, seq_len, seq_len, "prefill")[0]
        if blk.kind == "moe" and cfg.moe_dispatch != "held":
            analytic = seq_len * cfg.top_k * cfg.capacity_factor
            slots = cfg.n_experts * moe_capacity_slots(cfg, seq_len)
            fl += 2 * max(slots - analytic, 0.0) * 3 * cfg.d_model \
                * cfg.d_ff_expert
        return fl
    rep = sum(f(b) for b in cfg.pattern)
    return ([0.0] + [f(b) for b in cfg.head_blocks]
            + [rep] * cfg.n_repeats
            + [f(b) for b in cfg.tail]
            + [2.0 * seq_len * cfg.d_model * cfg.vocab])


def _iter_bench_history(path):
    """Yield parsed BENCH_history.jsonl entries, skipping malformed lines
    (the file is append-only across heterogeneous tool versions)."""
    import json
    import os
    if not os.path.exists(path):
        return
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                entry = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(entry, dict):
                yield entry


@dataclasses.dataclass(frozen=True)
class SuffixCostModel:
    """Per-site decision: suffix-mode (prefix once + vmapped suffix) vs the
    full-forward backends, for a chunk of ``n`` candidates cutting at a
    site with ``prefix_fraction`` f of forward FLOPs above it.

    Per-chunk cost ratio:  suffix / full = ((f - c) + (1 - f)·n) / n, where
    ``c`` is the prefix fraction already resident in the evaluator's trie
    (``covered``) — always <1 for n > 1 even cold, so the analytic *model*
    says "always suffix"; the thresholds price what it can't see: a shallow
    cut's win (f·(n-1) forwards) is smaller than its fixed overheads (one
    extra jit per segment, the cached-acts residency, per-chunk plan/slice
    work), so those sites fall back to the full path (``use_suffix() ==
    False`` -> the evaluator's inner batched/sharded/pipelined backend
    evaluates the chunk).

    ``measured`` switches the decision from the analytic threshold to
    observed hardware behavior: a tuple of ``(prefix_fraction, speedup,
    chunk)`` points calibrated from ``BENCH_history.jsonl``
    (:meth:`calibrated` — EWMA per site over matching config fingerprints,
    with the analytic ratio as the cold-start prior via an implicit
    ``(0.0, 1.0)`` anchor).  Suffix mode then runs wherever the
    interpolated measured speedup clears ``min_speedup``; the 5% margin
    absorbs dispatch overheads the FLOPs ratio can't see.
    """

    min_prefix_fraction: float = 0.05   # below this the reuse is noise
    min_chunk: int = 2                  # n=1 reuses nothing
    min_speedup: float = 1.05           # measured-mode margin over full path
    measured: Optional[Tuple[Tuple[float, float, int], ...]] = None

    def speedup(self, prefix_fraction: float, n: int,
                covered: float = 0.0) -> float:
        """Predicted candidates/sec gain of suffix mode for one chunk;
        ``covered`` discounts prefix work already cached in the trie."""
        f = min(max(prefix_fraction, 0.0), 1.0)
        c = min(max(covered, 0.0), f)
        return n / max((f - c) + (1.0 - f) * n, 1e-9)

    def predicted_speedup(self, prefix_fraction: float, n: int,
                          covered: float = 0.0) -> float:
        """Measured-mode estimate: linear interpolation over the calibrated
        ``(frac, speedup)`` points — anchored at (0, 1): zero prefix means
        zero reuse — rescaled by the analytic ratio to the requested chunk
        size and trie coverage (measurements are cold-trie, per-config
        chunk)."""
        if not self.measured:
            return self.speedup(prefix_fraction, n, covered)
        f = min(max(prefix_fraction, 0.0), 1.0)
        pts = sorted(((0.0, 1.0, n),) + tuple(self.measured))
        hi = next((p for p in pts if p[0] >= f), None)
        lo = next((p for p in reversed(pts) if p[0] <= f), pts[0])
        if hi is None:
            base = lo
        elif hi[0] == lo[0]:
            base = hi
        else:
            w = (f - lo[0]) / (hi[0] - lo[0])
            base = (f, lo[1] + w * (hi[1] - lo[1]),
                    int(round(lo[2] + w * (hi[2] - lo[2]))) or n)
        n0 = max(int(base[2]), 1)
        scale = self.speedup(f, n, covered) / max(self.speedup(f, n0), 1e-9)
        return base[1] * scale

    def use_suffix(self, prefix_fraction: float, n: int,
                   covered: float = 0.0) -> bool:
        if n < self.min_chunk:
            return False
        if self.measured:
            return (self.predicted_speedup(prefix_fraction, n, covered)
                    >= self.min_speedup)
        return prefix_fraction >= self.min_prefix_fraction

    @classmethod
    def calibrated(cls, history_path, *, fingerprint: Optional[dict] = None,
                   alpha: float = 0.5, **kwargs) -> "SuffixCostModel":
        """Calibrate from ``BENCH_history.jsonl``'s per-depth measurements.

        Walks the history oldest-first, EWMA-folding (weight ``alpha`` on
        the newer sample) each site's measured suffix-vs-batched speedup —
        only rows the evaluator actually ran in suffix mode (``mode ==
        "suffix"``), and only entries whose config matches ``fingerprint``
        on every key the entry carries (model / device / eval-batch changes
        must not pollute each other's rates).  Legacy history lines without
        ``per_site_depth`` are skipped, so an empty or pre-measurement file
        degrades to the pure analytic model (``measured=None``)."""
        ewma: dict = {}
        for entry in _iter_bench_history(history_path):
            cfg = entry.get("config") or {}
            if fingerprint and any(k in cfg and cfg[k] != v
                                   for k, v in fingerprint.items()):
                continue
            rows = entry.get("per_site_depth")
            if not isinstance(rows, dict):
                continue
            chunk = int(cfg.get("chunk_size") or 0)
            for row in rows.values():
                if not isinstance(row, dict) or row.get("mode") != "suffix":
                    continue
                try:
                    site = row["site"]
                    frac = float(row["prefix_fraction"])
                    sp = float(row["speedup_suffix_vs_batched"])
                except (KeyError, TypeError, ValueError):
                    continue
                prev = ewma.get(site)
                if prev is not None:
                    sp = (1 - alpha) * prev[1] + alpha * sp
                    chunk = chunk or prev[2]
                ewma[site] = (frac, sp, chunk)
        measured = tuple(sorted((f, s, max(c, 1)) for f, s, c in
                                ewma.values())) or None
        return cls(measured=measured, **kwargs)


def analytic_cell(cfg, shape, mode: str, *, remat: bool = True):
    """Analytic (HLO-faithful) FLOPs and HBM bytes for one cell, GLOBAL.

    Needed because XLA's cost_analysis counts a while-loop (layer-scan) body
    ONCE — it undercounts scanned stacks by ~n_repeats× (validated against an
    unrolled small model in tests/test_roofline.py).  Counts matmul FLOPs as
    2mnk, attention with the causal 1/2 factor, MoE at capacity (the real
    dispatched compute incl. padding waste), and the chunked linear-attention
    intra-chunk matmuls for mamba/rwkv (block_fwd_flops owns the per-block
    arithmetic).

    Bytes model (per step, global): weights read (fwd + bwd + remat re-fwd for
    train) + optimizer state r/w (train) + activation stream traffic
    (c·tokens·d per layer) + logits/CE traffic + cache reads (decode).
    """
    B, S = shape.global_batch, shape.seq_len
    d, V = cfg.d_model, cfg.vocab
    if mode == "decode":
        new_tokens, ctx = B * 1, S
    else:
        new_tokens, ctx = B * S, S
    kinds = ([b for b in cfg.head_blocks]
             + [b for b in cfg.pattern] * cfg.n_repeats
             + list(cfg.tail))

    f_layer = 0.0       # forward flops for all layers, per step (global)
    w_bytes = 0.0       # weight bytes (bf16), all layers
    cache_bytes = 0.0   # decode-state bytes read per step
    for blk in kinds:
        f, wb, cb = block_fwd_flops(cfg, blk, new_tokens, ctx, mode)
        f_layer += f
        w_bytes += wb
        cache_bytes += cb

    f_logits = 2 * new_tokens * d * V
    w_bytes += V * d * 2
    fwd = f_layer + f_logits

    if mode == "train":
        flops = fwd * (4 if remat else 3)          # fwd + re-fwd + 2×bwd
        # bytes: weights ×(2 fwd reads incl remat + 2 bwd) + grads + adam f32
        nparams = w_bytes / 2
        opt_bytes = nparams * (4 + 8 + 8 + 4 + 4)  # grad w + m/v rw + p rw
        act_bytes = 8 * new_tokens * d * len(kinds) * 2
        logit_bytes = 3 * new_tokens * V * 4   # f32 logits + CE fwd/bwd
        hbm = w_bytes * 3 + opt_bytes + act_bytes + logit_bytes
    else:
        flops = fwd
        act_bytes = 4 * new_tokens * d * len(kinds) * 2
        hbm = w_bytes + act_bytes + cache_bytes \
            + new_tokens * V * 2
    return flops, hbm


def active_params(cfg) -> float:
    """Parameter count with only top_k routed experts counted (MoE)."""
    d, f, V, L = cfg.d_model, cfg.d_ff, cfg.vocab, cfg.n_layers
    total = V * d  # embed (tied head)
    kinds = ([b.kind for b in cfg.head_blocks]
             + [b.kind for b in cfg.pattern] * cfg.n_repeats
             + [b.kind for b in cfg.tail])
    attn_p = d * (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim \
        + cfg.n_heads * cfg.head_dim * d
    ffn_p = d * f * (3 if cfg.gated_ffn else 2)
    moe_p = (cfg.top_k * 3 * d * cfg.d_ff_expert + d * cfg.n_experts
             + (3 * d * cfg.d_ff_shared if cfg.n_shared_experts else 0))
    di = cfg.d_inner
    mamba_p = d * 2 * di + d * (2 * cfg.ssm_state) + di * d
    rwkv_p = 6 * d * d + 2 * d * f
    per = {"dense": attn_p + ffn_p, "moe": attn_p + moe_p,
           "attn_only": attn_p, "mamba": mamba_p, "rwkv": rwkv_p}
    total += sum(per[k] for k in kinds)
    return float(total)
