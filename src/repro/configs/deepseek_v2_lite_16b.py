"""DeepSeek-V2-Lite 16B [arXiv:2405.04434], as published in
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json.

27L d_model=2048 16H vocab=102400; MLA (no q compression, kv_lora=512,
qk_nope=128, qk_rope=64, v=128) with RMSNorm on the latent and YaRN rope
(factor 40 over 4096 original positions, beta 32/1, mscale 0.707 for both);
layer 0 dense (d_ff=10944), then MoE: 64 routed experts of width 1408,
top-6 by float32 softmax, gates not renormalized, scaling factor 1, plus 2
shared experts. RMSNorm eps 1e-6, untied head.

``EP4`` is one chip's share of a four-chip expert-parallel host: each chip
holds 16 of the 64 routed experts of every MoE layer (this one ids 0-15)
and replicates everything else (attention, the dense layer, the shared
experts, the router, embedding and head).
"""
import dataclasses

from repro.models.config import ArchConfig, MLAConfig, MoEConfig, RopeScaling

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b",
    arch_type="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    rope_theta=10000.0,
    rope_scaling=RopeScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_expert=1408,
                  moe_period=1, first_dense=1, norm_topk_prob=False,
                  routed_scaling_factor=1.0),
    mlp_variant="swiglu",
    norm_eps=1e-6,
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
)

EP4 = dataclasses.replace(
    CONFIG, name="deepseek-v2-lite-16b-ep4",
    moe=dataclasses.replace(CONFIG.moe, n_held=16, held_offset=0))
