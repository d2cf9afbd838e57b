"""granite-4.0-h-micro [hf:ibm-granite/granite-4.0-h-micro; hf].

40 layers at d_model=2048: Mamba-2 mixers (d_state 128, 64 heads of 64,
expand 2, one group, conv 4 with bias, chunk 256) with one GQA attention
layer (32H, kv=8, head_dim 64, NoPE) at positions 5, 15, 25 and 35, so
the pattern "mmmmmammmm" repeats four times; every layer has a SwiGLU
MLP of width 8192. muP-style multipliers: embedding 12, residual 0.22,
attention 1/64 (in place of 1/sqrt(head_dim)), logits divided by 8.
Tied embeddings over a 100,352-token vocabulary.
"""
from repro.configs.base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    vocab_size=100352,
    head_dim=64,
    rope_style="none",
    attention_multiplier=0.015625,
    ssm_state=128,
    ssm_expand=2,
    ssm_conv=4,
    ssm_head_dim=64,
    ssm_conv_bias=True,
    block_pattern="mmmmmammmm",
    tie_embeddings=True,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    subquadratic=False,      # four full-attention layers
))
