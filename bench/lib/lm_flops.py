"""FLOPs and bytes of the DeepSeek-V2 block computed from its configuration
(published ``config.json`` keys; 2 FLOPs per multiply-add; float32 operands,
4 bytes an element).

These count the work the configuration requires, independent of how the
program schedules it: matmuls and attention scores and values; norms,
activations, softmax and routing bookkeeping (well under 1%) are not
counted.  Attention is causal: a query at position ``i`` of a sequence of
``S`` reads ``i + 1`` keys, ``(S + 1) / 2`` on average.  The held experts'
work follows the rows routed to them (``rows_per_token``: (token, k) pairs
that land on a held expert, per token), measured by the program
(``moe.rows_held``) or, without a measurement, ``k · held / experts``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

F32 = 4


def _n_dense(cfg: dict) -> int:
    return cfg["first_k_dense_replace"]


def n_moe(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - _n_dense(cfg)


def uniform_rows_per_token(cfg: dict) -> float:
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg.get(
        "router_experts", cfg["n_routed_experts"])


def mla_token(cfg: dict, seq: int) -> float:
    """Latent attention per token of a causal sequence of ``seq``."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    proj = d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d
    keys = (seq + 1) / 2
    return 2.0 * proj + 2.0 * H * (dn + dr + dv) * keys


def ffn_token(cfg: dict, width: int) -> float:
    """A SwiGLU FFN of ``width`` per token (gate, up, down)."""
    return 2.0 * 3 * cfg["hidden_size"] * width


def experts_token(cfg: dict, rows_per_token: float) -> float:
    """The held experts per token, for ``rows_per_token`` routed rows."""
    return rows_per_token * ffn_token(cfg, cfg["moe_intermediate_size"])


def moe_token(cfg: dict, seq: int, rows_per_token: float) -> float:
    """One expert layer per token: attention, router (every routed
    expert), held experts, shared experts."""
    d = cfg["hidden_size"]
    return (mla_token(cfg, seq)
            + 2.0 * d * cfg.get("router_experts", cfg["n_routed_experts"])
            + experts_token(cfg, rows_per_token)
            + ffn_token(cfg, cfg["moe_intermediate_size"]
                        * cfg["n_shared_experts"]))


def segments(cfg: dict, seq: int, rows: Optional[Sequence[float]] = None
             ) -> List[Tuple[str, float]]:
    """``[(segment, forward FLOPs per token)]`` in forward order:
    ``layer0`` … ``layer<n-1>`` and ``head``; ``rows`` gives each expert
    layer's rows per token (default uniform)."""
    out = []
    for i in range(_n_dense(cfg)):
        out.append((f"layer{i}", mla_token(cfg, seq)
                    + ffn_token(cfg, cfg["intermediate_size"])))
    for j in range(n_moe(cfg)):
        rpt = uniform_rows_per_token(cfg) if rows is None else rows[j]
        out.append((f"layer{_n_dense(cfg) + j}", moe_token(cfg, seq, rpt)))
    out.append(("head", 2.0 * cfg["hidden_size"] * cfg["vocab_size"]))
    return out


def forward_token(cfg: dict, seq: int, rows=None) -> float:
    """Forward FLOPs per token of the whole network."""
    return sum(f for _, f in segments(cfg, seq, rows))


def suffix_token(cfg: dict, seq: int, rows=None) -> Dict[str, float]:
    """segment -> forward FLOPs per token from that segment to the head."""
    out, tail = {}, 0.0
    for name, f in reversed(segments(cfg, seq, rows)):
        tail += f
        out[name] = tail
    return out


def experts_call(cfg: dict, rows: float) -> Tuple[float, float]:
    """(FLOPs, least bytes) of one forward pass of the held experts'
    grouped matmuls over ``rows`` sorted rows: gate, up and down
    projections; every held expert's three matrices read once, the rows
    read once and written once (the F-wide intermediates could stay on
    the chip)."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = 2.0 * rows * 3 * d * f
    nbytes = F32 * (3 * cfg["n_routed_experts"] * d * f + 2 * rows * d)
    return flops, nbytes
