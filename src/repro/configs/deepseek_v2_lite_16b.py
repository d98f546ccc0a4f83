"""deepseek-v2-lite-16b [moe] — MLA + fine-grained MoE.

27L d_model=2048 16H d_ff(dense L0)=10944 vocab=102400; MLA kv_lora=512,
no q LoRA; YaRN rope (factor 40 over 4096 positions, mscale 0.707 on both,
so the softmax scale is 192^-0.5 * (0.1 * 0.707 * ln 40 + 1)^2); MoE: 64
routed top-6 + 2 shared, d_expert=1408, first layer dense.  Routing is a
softmax over all 64 routed experts in float32, greedy top-6, the weights
kept as they are (``norm_topk_prob`` false, ``routed_scaling_factor`` 1).
[arXiv:2405.04434; hf config.json]

Note: the assignment brief lists both "64e top-6" and "2 shared+160
routed"; 160 routed belongs to full V2 — we use the V2-*Lite* values
(64 routed) per the primary spec; DESIGN.md §14 states the routing.
"""

from repro.models.config import (
    BlockSpec,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    YarnConfig,
)

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,  # qk_nope + qk_rope (bookkeeping; MLA dims below rule)
    d_ff=10944,  # the single dense layer
    vocab=102400,
    prelude=(BlockSpec(mixer="mla", ffn="dense"),),
    pattern=(BlockSpec(mixer="mla", ffn="moe"),),
    n_periods=26,
    act="silu",
    rope_theta=10000.0,
    rope_scaling=YarnConfig(
        factor=40.0,
        original_max_position=4096,
        beta_fast=32.0,
        beta_slow=1.0,
        mscale=0.707,
        mscale_all_dim=0.707,
    ),
    mla=MLAConfig(
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    moe=MoEConfig(
        n_experts=64,
        top_k=6,
        d_expert=1408,
        n_shared=2,
        normalize_top_k=False,
        capacity_factor=1.25,
    ),
)
