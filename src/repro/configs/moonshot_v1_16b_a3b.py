"""Moonlight-16B-A3B (Moonshot AI), DeepSeek-V3's architecture at 16B
[https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json]:
27 layers of multi-head latent attention (16 heads, KV latent of rank 512,
128 + 64 query/key dimensions, 128 value dimensions, no query compression);
layer 0 has a dense SwiGLU MLP of width 11,264, layers 1-26 hold 64 routed
experts of width 1,408 (6 per token, sigmoid scores with a correction
bias, normalised and scaled by 2.446) and 2 shared experts."""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,
    vocab_size=163840,
    num_experts=64,
    experts_per_token=6,
    num_shared_experts=2,
    router_aux_coef=1e-4,
    topk_method="noaux_tc",
    routed_scaling_factor=2.446,
    first_k_dense_replace=1,
    intermediate_size=11264,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    block_pattern=("moe",),
    rope_theta=50000.0,
    norm_eps=1e-5,
    tie_embeddings=False,
    embed_scale=False,
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json",
)
