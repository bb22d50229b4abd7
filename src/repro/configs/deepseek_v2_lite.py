"""deepseek-v2-lite — latent attention (MLA) and 64 sparse experts.

[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite] 27L
d_model=2048 16H, MLA without query compression: kv_lora_rank 512,
qk_nope_head_dim 128, qk_rope_head_dim 64, v_head_dim 128. Layer 0 is a
dense SwiGLU of width 10944; layers 1-26 route each token to 6 of 64
experts of width 1408 (softmax, greedy, gates not renormalised, scale 1)
beside 2 shared experts (one SwiGLU of width 2816). RMSNorm eps 1e-6,
rope theta 10000 with YaRN (factor 40 over 4096 original positions,
beta 32 / 1, mscale = mscale_all_dim = 0.707), untied head, vocab 102400.
The latent stack routes dropless (``moe.moe_dropless``), so the serving
engine prefills it in batched chunks.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    arch_id="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,
    d_ff=1408,
    vocab_size=102400,
    n_experts=64,
    top_k=6,
    n_shared_experts=2,
    norm_topk_prob=False,
    routed_scaling=1.0,
    first_k_dense=1,
    dense_d_ff=10944,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10_000.0,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm_eps=1e-6,
    citation="arXiv:2405.04434",
)
