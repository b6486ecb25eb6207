"""deepseek-v2-lite  [arXiv:2405.04434; HF deepseek-ai/DeepSeek-V2-Lite].

27L d_model=2048 16H, MLA (kv_lora_rank 512, no q compression, qk_nope 128,
qk_rope 64, v 128), YaRN rope (factor 40 over 4096, beta 32/1, mscale
0.707 = mscale_all_dim, theta 1e4).  Layer 0 dense (SwiGLU 10944); layers
1-26 MoE: 64 routed experts of width 1408, greedy softmax top-6, no
renormalisation, routed_scaling_factor 1, plus 2 shared experts (one
SwiGLU of width 2816).  RMSNorm eps 1e-6, untied head, vocab 102400.

`moe_held` = 64 here: the whole layer on one device.  A chip of an
expert-parallel deployment holds a share (the benchmark's configuration
holds 8, `moe_share` 0).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab_size=102400,
    norm_type="rmsnorm", mlp_act="silu", gated_mlp=True,
    rope_theta=1e4, tie_embeddings=False,
    moe_experts=64, moe_top_k=6, moe_d_ff=1408, moe_shared_d_ff=2816,
    moe_norm_topk=False, moe_routed_scale=1.0, moe_held=64, moe_share=0,
    dense_layers=1,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    rope_yarn_factor=40.0, rope_yarn_original=4096,
    rope_yarn_beta_fast=32.0, rope_yarn_beta_slow=1.0,
    rope_yarn_mscale=0.707, rope_yarn_mscale_all_dim=0.707,
    source="arXiv:2405.04434",
)


def smoke_config() -> ModelConfig:
    """Three layers (one dense, two MoE) at toy widths; 8 experts, top 3,
    of which a chip holds 4; YaRN over an original 64 positions."""
    return CONFIG.replace(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=96,
        vocab_size=256, moe_experts=8, moe_top_k=3, moe_d_ff=32,
        moe_shared_d_ff=64, moe_held=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_yarn_original=64, remat=False)
