"""granite-4.0-h-micro [hybrid] — Mamba-2 mixers with four NoPE GQA layers.

[hf:ibm-granite/granite-4.0-h-micro config.json; GraniteMoeHybrid] 40
layers, attention at layers 5, 15, 25 and 35, Mamba-2 elsewhere (d_inner
4,096 as 64 heads of 64, d_state 128, one group, conv width 4, chunk 256);
every layer has a dense SwiGLU MLP of 8,192. No position embedding; score
scale 1/64; embeddings x12, each branch x0.22, logits /8; tied embedding.
"""
from repro.configs.base import ModelConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100_352,
    ssm_state=128,
    ssm_expand=2,
    ssm_headdim=64,
    ssm_conv_width=4,
    ssm_chunk=256,
    layer_types=_PERIOD * 4,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    attention_multiplier=0.015625,
    position_embedding="nope",
    norm_eps=1e-5,
    tie_embeddings=True,
)
