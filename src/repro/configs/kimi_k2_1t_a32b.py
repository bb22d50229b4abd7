"""kimi-k2-1t-a32b — trillion-parameter MoE (paper-table cloud-scale executor).

[arXiv:2501.kimi2] 61L d_model=7168 64H d_ff=2048 (per expert)
vocab=163840, MoE 384 experts top-8 (+1 shared expert). The published
model uses latent attention (MLA, as deepseek-v2-lite does); this config
stands it in with GQA attention (kv=8, head_dim 128) on the capacity-routed
expert path, for training and dry runs only. It is not moved onto the
repo's MLA stack.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    arch_id="kimi-k2-1t-a32b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=64,
    n_kv_heads=8,
    head_dim=128,
    d_ff=2048,
    vocab_size=163840,
    n_experts=384,
    top_k=8,
    n_shared_experts=1,
    rope_theta=1_000_000.0,
    citation="arXiv:2501.kimi2",
)
