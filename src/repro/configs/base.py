"""Model/config schema shared by all architectures and the launcher."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.core.cim_layers import CIMConfig


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0             # 0 -> d_model // n_heads
    # attention flavour
    qkv_bias: bool = False
    norm_type: str = "rmsnorm"    # rmsnorm | layernorm | nonparam_ln
    sliding_window: int = 0       # SWA (mixtral); 0 = full attention
    rope_theta: float = 1e6
    mlp_act: str = "silu"
    gated_mlp: bool = True
    tie_embeddings: bool = False
    # MoE
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_d_ff: int = 0             # expert width; 0 -> d_ff
    moe_shared_d_ff: int = 0      # shared experts' SwiGLU width; 0 = none
    moe_norm_topk: bool = True    # renormalise the top-k route weights
    moe_routed_scale: float = 1.0  # route weights x this (routed_scaling)
    moe_held: int = 0             # >0: a dropless layer that holds experts
    moe_share: int = 0            # [share*held, (share+1)*held) of them
    dense_layers: int = 0         # leading dense-FFN layers of a moe stack
    # latent attention (MLA, DeepSeek-V2); kv_lora_rank 0 = per-head K/V
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope scaling (arXiv:2309.00071); factor 0 = plain rope
    rope_yarn_factor: float = 0.0
    rope_yarn_original: int = 4096
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_mscale: float = 1.0
    rope_yarn_mscale_all_dim: float = 0.0
    # hybrid (recurrentgemma / griffin)
    attn_every: int = 0           # every k-th layer is local attention
    local_window: int = 2048
    lru_width: int = 0
    # ssm (mamba2 / SSD)
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    conv_width: int = 4
    # enc-dec (whisper)
    encoder_layers: int = 0
    max_target_len: int = 448
    # vlm
    vision_tokens: int = 0        # prefix patch embeddings (stub frontend)
    # execution
    cim: CIMConfig = CIMConfig(mode="bypass")
    dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "full"    # full | dots (save dot outputs in bwd)
    attn_impl: str = "jnp"        # jnp | pallas (fused flash kernels)
    # source provenance (paper/hf tag from the assignment)
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

# archs with at least one sub-quadratic decode path run long_500k
SUBQUADRATIC = {"mixtral-8x22b", "recurrentgemma-2b", "mamba2-1.3b"}


def shape_applicable(arch: str, shape: str, family: str) -> Tuple[bool, str]:
    if shape == "long_500k" and arch not in SUBQUADRATIC:
        return False, "pure full-attention arch: 500k decode skipped (DESIGN.md §4)"
    return True, ""
