"""deepseek-v2-lite-16b [arXiv:2405.04434].

27L d_model=2048 16H, MLA kv_lora=512 (no q-lora in Lite), rope 64 +
nope 128 head dims, v_head 128; MoE: 64 routed + 2 shared experts,
top-6 by greedy softmax with unnormalised, unscaled gates
(routed_scaling_factor 1), expert d_ff=1408; first layer dense FFN
(10944).  RoPE with YaRN (factor 40 over 4096 positions), as the
published config and modeling_deepseek.py state.  The published rope
columns pair (2i, 2i+1); the program rotates halves (i, i+32), which is
the same attention once a checkpoint's q and k_pe rope columns are put
in that order.
"""
from .base import ModelConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    vocab_size=102_400,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,            # qk_nope + qk_rope
    d_ff=1408,
    attn_kind="mla",
    q_lora_rank=0,
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    d_expert=1408,
    first_dense_layers=1,
    d_ff_dense=10_944,
    rope_theta=10_000.0,
    rope_scaling=YarnScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0,
                             mscale=0.707, mscale_all_dim=0.707),
    norm_topk_prob=False,
    act="silu",
    tie_embeddings=False,
    skip_shapes=("long_500k",),
)
