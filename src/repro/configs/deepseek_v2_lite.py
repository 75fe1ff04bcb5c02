"""DeepSeek-V2-Lite — latent attention (MLA, no query compression), 2 shared
+ 64 routed experts top-6, one leading dense layer [arXiv:2405.04434].

Values from https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite (config.json):
softmax router with greedy top-6 and no renormalization of the gates
(``norm_topk_prob`` false, ``routed_scaling_factor`` 1), YaRN rope on the 64
rope dimensions, untied output head.  The experts go through the drop-free
'held' dispatch; ``CONFIG.chip_share()`` is one chip's share of an 8-way
expert-parallel deployment.
"""
from .base import ArchConfig, Block, Yarn

CONFIG = ArchConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=10944,
    vocab=102400, head_dim=192,
    n_experts=64, top_k=6, d_ff_expert=1408,
    n_shared_experts=2, d_ff_shared=2816, norm_topk=False,
    moe_dispatch="held",
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    yarn=Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
              beta_slow=1.0, mscale_all_dim=0.707),
    head_blocks=(Block("dense"),),
    pattern=(Block("moe"),), act="silu", tie_embeddings=False,
)
