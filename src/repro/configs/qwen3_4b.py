"""qwen3-4b — qk_norm, GQA [hf:Qwen/Qwen3-4B].

As published in Qwen3-4B's ``config.json`` (Qwen3 Technical Report,
arXiv:2505.09388): 36L d_model=2560 32H (kv=8) head_dim=128 (so the query
is 4096 wide), d_ff=9728, vocab=151936, rms_norm_eps=1e-6,
rope_theta=1e6, RMSNorm on q and k per head, no q/k/v bias, and the
output head tied to the embedding: 4,022,468,096 parameters.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-4b",
    family="dense",
    n_layers=36,
    d_model=2560,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=9728,
    vocab=151936,
    rope_theta=1_000_000.0,
    qk_norm=True,
    tie_embeddings=True,
    source="hf:Qwen/Qwen3-4B",
)
