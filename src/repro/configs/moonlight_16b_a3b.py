"""Moonlight-16B-A3B: DeepSeek-V3 architecture, MLA without q-LoRA, 64
routed experts top-6 + 2 shared, sigmoid router with a selection-only
correction bias (noaux_tc), one leading dense layer.

[hf: moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3].
27 layers, hidden 2048, 16 heads, kv_lora_rank 512, qk_nope 128, qk_rope
64, v_head 128, dense width 11264, expert width 1408, routed scaling
2.446, vocabulary 163840 untied, rope theta 50000, no MTP layer.
"""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    head_dim=128, d_ff=11264, vocab=163840,
    n_experts=64, n_shared_experts=2, top_k=6, moe_d_ff=1408,
    first_k_dense=1, routed_scaling_factor=2.446, router_bias=True,
    use_mla=True, q_lora_rank=0, kv_lora_rank=512,
    qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    mtp_depth=0, rope_theta=5e4, norm_eps=1e-5,
)
