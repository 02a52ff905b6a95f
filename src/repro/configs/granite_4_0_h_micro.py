"""granite-4.0-h-micro [hybrid] — Mamba-2 + NoPE GQA, dense
[hf:ibm-granite/granite-4.0-h-micro config.json, model_type granitemoehybrid].

40 layers in four periods of ``[mamba x5, attention, mamba x4]``; every
layer is a mixer followed by its own SwiGLU MLP of width 8192, both
residual branches scaled by ``residual_multiplier`` 0.22.  Mamba-2: 64
heads of 64 (``expand`` 2), state 128, one group, conv 4 with bias, chunk
256.  Attention: 32 query / 8 KV heads of 64, no position encoding, softmax
scale ``attention_multiplier`` 1/64.  Embedding x12, logits / 8, tied
100,352-entry vocabulary, RMSNorm eps 1e-5.
"""
from repro.configs import ArchConfig

CONFIG = ArchConfig(
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
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_chunk=256,
    layer_pattern=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
    ssm_conv_bias=True,
    positional="nope",
    attention_scale=1.0 / 64,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    norm_eps=1e-5,
    tie_embeddings=True,
)
