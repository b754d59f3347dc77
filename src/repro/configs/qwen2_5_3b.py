"""Qwen2.5-3B [hf:Qwen/Qwen2.5-3B config.json] — dense GQA with QKV bias.

36L d_model=2048 16H (GQA kv=2) d_ff=11008 vocab=151936, tied embeddings,
RMSNorm eps 1e-6, RoPE theta 1e6.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen2.5-3b",
    family="dense",
    n_layers=36,
    d_model=2048,
    n_heads=16,
    n_kv_heads=2,
    d_ff=11008,
    vocab_size=151_936,
    rope_theta=1_000_000.0,
    rmsnorm_eps=1e-6,
    qkv_bias=True,
    tie_embeddings=True,
)

SMOKE = CONFIG.replace(
    name="qwen2.5-3b-smoke",
    n_layers=2,
    d_model=256,
    n_heads=8,
    n_kv_heads=2,
    d_ff=512,
    vocab_size=512,
)
