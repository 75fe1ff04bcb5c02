"""Architecture config system: ArchConfig, input shapes, registry.

Every assigned architecture is a ``configs/<id>.py`` exporting ``CONFIG``.
Backbones are built from a repeating ``pattern`` of Blocks (scan-compiled),
plus optional unrolled ``head_blocks`` (before) and an automatic tail (the
``n_layers % len(pattern)`` remainder, taken from the pattern prefix).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Block:
    kind: str                      # dense | moe | mamba | rwkv | attn_only
    window: Optional[int] = None   # sliding-window size for this block's attn
    rope_theta: float = 1e4
    shared: bool = False           # share params across repeats (zamba2 attn)


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rope scaling (Peng et al. 2023, arXiv:2309.00071), in the form
    of DeepSeek-V2's ``rope_scaling`` (``type: yarn``).  Its ``mscale``
    scales cos and sin by ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``, which is 1 where the two are equal (DeepSeek-V2-Lite:
    both 0.707), the one case held here."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int
    pattern: Tuple[Block, ...]
    head_blocks: Tuple[Block, ...] = ()
    act: str = "silu"
    gated_ffn: bool = True
    qk_norm: bool = False
    # moe
    n_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    n_shared_experts: int = 0
    d_ff_shared: int = 0
    capacity_factor: float = 1.25
    norm_topk: bool = True         # renormalize the top-k gates to sum to 1
    # experts this chip holds, [expert_offset, expert_offset + n); 0 = all
    # (only the drop-free 'held' dispatch holds a share)
    n_experts_held: int = 0
    expert_offset: int = 0
    # latent attention (MLA, DeepSeek-V2 §2.1) when kv_lora_rank > 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    yarn: Optional[Yarn] = None    # rope scaling of the MLA rope part
    # ssm
    ssm_state: int = 0
    mamba_head_dim: int = 64
    rwkv_head_dim: int = 64
    # performance knobs (§Perf hillclimb variants; defaults = baseline)
    # 'scatter' | 'gather' (capacity-bounded) | 'held' (drop-free, the
    # experts this chip holds; see models.moe)
    moe_dispatch: str = "scatter"
    remat_group: int = 1           # layers per remat group in the train scan
    # io / modality
    prefix_len: int = 0            # stubbed frontend embeddings (vlm)
    subquadratic: bool = False     # eligible for long_500k
    tie_embeddings: bool = True
    dtype: str = "bfloat16"
    # activation replacement mode when masked: 'identity' | 'poly2'
    act_when_masked: str = "identity"

    @property
    def n_repeats(self) -> int:
        return (self.n_layers - len(self.head_blocks)) // len(self.pattern)

    @property
    def tail(self) -> Tuple[Block, ...]:
        rem = (self.n_layers - len(self.head_blocks)) % len(self.pattern)
        return self.pattern[:rem]

    @property
    def d_inner(self) -> int:      # mamba inner width
        return 2 * self.d_model

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    def chip_share(self, *, moe_layers: int = 4, ways: int = 8,
                   shard: int = 0) -> "ArchConfig":
        """One chip's share of a deployment in which every expert layer is
        divided over ``ways`` chips by expert and the vocabulary ``ways``
        ways: every width as published, the leading dense layers and
        ``moe_layers`` expert layers (the layers cut away would be further
        pipeline stages), the experts of shard ``shard`` (the router still
        scores all of them), and that shard's slice of the vocabulary."""
        if self.moe_dispatch != "held":
            raise ValueError(f"{self.name}: a chip's share of experts needs "
                             "the 'held' MoE dispatch")
        if self.n_experts % ways or self.vocab % ways:
            raise ValueError(f"{self.name}: {self.n_experts} experts or "
                             f"{self.vocab} ids do not split {ways} ways")
        held = self.n_experts // ways
        return dataclasses.replace(
            self, n_layers=len(self.head_blocks) + moe_layers,
            n_experts_held=held, expert_offset=shard * held,
            vocab=self.vocab // ways)

    def reduced(self) -> "ArchConfig":
        """Small same-family config for CPU smoke tests."""
        pat = self.pattern
        nl = len(self.head_blocks) + 2 * len(pat) + len(self.tail)
        return dataclasses.replace(
            self, n_layers=nl, d_model=64,
            n_heads=4, n_kv_heads=min(self.n_kv_heads, 2), head_dim=16,
            d_ff=96, vocab=128,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            d_ff_expert=32 if self.n_experts else 0,
            d_ff_shared=32 if self.n_shared_experts else 0,
            ssm_state=8 if self.ssm_state else 0,
            mamba_head_dim=16, rwkv_head_dim=16,
            prefix_len=8 if self.prefix_len else 0,
            dtype="float32",
            n_experts_held=0, expert_offset=0,
            kv_lora_rank=32 if self.mla else 0,
            qk_nope_head_dim=16 if self.mla else 0,
            qk_rope_head_dim=8 if self.mla else 0,
            v_head_dim=16 if self.mla else 0,
        )


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    mode: str                      # 'train' | 'prefill' | 'decode'


SHAPES: Dict[str, ShapeCell] = {
    "train_4k": ShapeCell("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
}

ARCH_IDS = [
    "zamba2_2p7b", "stablelm_1p6b", "mistral_nemo_12b", "qwen3_32b",
    "gemma3_27b", "mixtral_8x22b", "deepseek_moe_16b", "rwkv6_3b",
    "paligemma_3b", "musicgen_large", "deepseek_v2_lite",
]


def get_config(arch_id: str) -> ArchConfig:
    arch_id = arch_id.replace("-", "_").replace(".", "p")
    mod = importlib.import_module(f"repro.configs.{arch_id}")
    return mod.CONFIG


def cell_applicable(cfg: ArchConfig, shape: str) -> Tuple[bool, str]:
    """Whether (arch, shape) is a runnable cell; reason if not."""
    if shape == "long_500k" and not cfg.subquadratic:
        return False, "pure full-attention arch: 512k decode is quadratic"
    return True, ""


def input_specs(cfg: ArchConfig, shape: ShapeCell):
    """ShapeDtypeStruct stand-ins for every model input (no allocation).

    train:   tokens/labels (B, S) (+ prefix_embeds for stub frontends;
             text length shrinks so total seq == shape.seq_len)
    prefill: tokens (B, S)
    decode:  token (B, 1) + cache handled by the step factory.
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    text = S - cfg.prefix_len
    specs = {}
    if shape.mode == "train":
        specs["tokens"] = jax.ShapeDtypeStruct((B, text), i32)
        specs["labels"] = jax.ShapeDtypeStruct((B, text), i32)
    elif shape.mode == "prefill":
        specs["tokens"] = jax.ShapeDtypeStruct((B, text), i32)
    else:  # decode: one new token, cache of length S
        specs["tokens"] = jax.ShapeDtypeStruct((B, 1), i32)
    if cfg.prefix_len and shape.mode != "decode":
        specs["prefix_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.prefix_len, cfg.d_model), f)
    return specs


def make_inputs(cfg: ArchConfig, shape: ShapeCell, seed: int = 0):
    """Concrete (small-RNG) inputs matching input_specs — for smoke tests."""
    rng = np.random.default_rng(seed)
    out = {}
    for k, sds in input_specs(cfg, shape).items():
        if sds.dtype == jnp.int32:
            out[k] = jnp.asarray(
                rng.integers(0, cfg.vocab, size=sds.shape, dtype=np.int32))
        else:
            out[k] = jnp.asarray(rng.normal(size=sds.shape) * 0.02,
                                 dtype=sds.dtype)
    return out
