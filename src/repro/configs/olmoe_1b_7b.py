"""olmoe-1b-7b [moe]: allenai/OLMoE-1B-7B-0924 as published (config.json;
arXiv:2409.02060). 16L d2048 16H (kv=16) of 128, SwiGLU experts of 1024,
64 experts top-8 without renormalising the top-k weights
(``norm_topk_prob: false``), dropless routing, QK-norm over the whole
2048-wide q and k projections, RMSNorm eps 1e-5, RoPE 10000, vocab 50304
untied, context 4096.

Fully expert-parallel (64 % 16 == 0) and head-parallel (16 % 16 == 0).
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="olmoe-1b-7b", family="moe",
    n_layers=16, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
    d_ff=1024, vocab_size=50304,
    pattern=("attn_moe",), n_experts=64, moe_top_k=8, qk_norm=True,
    norm_eps=1e-5, moe_norm_topk=False, moe_capacity=None,
    notes="long_500k skipped (full attention).",
)
