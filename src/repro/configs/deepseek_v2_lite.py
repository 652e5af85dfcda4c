"""DeepSeek-V2-Lite — latent attention (MLA) and DeepSeekMoE, one chip's
share of an 8-way expert-parallel deployment.

27L, d_model 2048, 16 heads, no q-LoRA: queries 128 + 64 (RoPE) per head,
a 512-wide KV latent plus one shared 64-wide RoPE key per token, values
128 per head; YaRN RoPE (theta 1e4, factor 40 over 4096 positions, mscale
0.707 on both sides). Layer 0 is a dense SwiGLU of 10944; layers 1-26
route over 64 experts of 1408 (softmax, greedy top-6, no renormalisation)
beside 2 shared experts. Vocab 102400, untied, RMSNorm eps 1e-6.
[hf:deepseek-ai/DeepSeek-V2-Lite] [arXiv:2405.04434] [arXiv:2309.00071]

This device holds routed experts 0-7 of every MoE layer: the 64 are split
over 8 chips by expert parallelism, and the router still scores all 64.
"""

from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    arch_type="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_head=192,
    d_ff=10944,
    vocab_size=102400,
    mlp_type="swiglu",
    attn_type="mla",
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    rope_theta=1e4,
    rope_factor=40.0,
    rope_original_max=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    n_experts=64,
    top_k=6,
    capacity_factor=0.0,
    moe_d_ff=1408,
    n_shared_experts=2,
    first_dense_layers=1,
    router_norm_topk=False,
    routed_scale=1.0,
    experts_held=(0, 8),
    norm_eps=1e-6,
    tie_embeddings=False,
    source="[hf:deepseek-ai/DeepSeek-V2-Lite]",
)
