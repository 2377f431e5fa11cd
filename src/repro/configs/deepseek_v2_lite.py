"""deepseek-v2-lite [mla_moe] — MLA, 64 routed + 2 shared experts, top-6
[hf:deepseek-ai/DeepSeek-V2-Lite]."""
import jax.numpy as jnp
from ..models.deepseek_v2 import DeepseekV2Config

FULL = DeepseekV2Config(
    name="deepseek-v2-lite", n_layers=27, d_model=2048, n_heads=16, vocab=102400,
    d_ff=10944, moe_d_ff=1408, n_experts_routed=64, experts_held=64, top_k=6,
    n_shared_experts=2, first_k_dense_replace=1, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    norm_topk_prob=False, routed_scaling_factor=1.0, rope_theta=1e4,
    rope_factor=40.0, rope_original_max=4096, beta_fast=32.0, beta_slow=1.0,
    mscale=0.707, mscale_all_dim=0.707, tie_embeddings=False, dtype=jnp.bfloat16,
)

# 8 of 64 routed experts held, so the tests can sum the 8 shares
SMOKE = DeepseekV2Config(
    name="deepseek-v2-smoke", n_layers=3, d_model=64, n_heads=4, vocab=512,
    d_ff=96, moe_d_ff=32, n_experts_routed=64, experts_held=8, top_k=6,
    n_shared_experts=2, first_k_dense_replace=1, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_factor=40.0, rope_original_max=64, mscale=0.707, mscale_all_dim=0.707,
    dtype=jnp.float32, remat=False,
)
