"""Granite-4.0-H-Micro — Mamba-2 layers with four NoPE GQA layers [hf:
ibm-granite/granite-4.0-h-micro config.json, model_type granitemoehybrid].

40L, d_model=2048: 36 Mamba-2 layers (64 heads of 64, d_state 128, one
group, conv width 4 with bias, chunk 256) and GQA attention at layers 5,
15, 25 and 35 (32 query heads, 8 K/V heads of 64, no position embedding).
Every mixer is followed by a SwiGLU MLP of 8192 (the config's
``shared_intermediate_size``; no experts).  muP multipliers: embeddings x12,
each residual branch x0.22, softmax scale 1/64, logits /8.  Tied
embeddings, vocab 100352, RMSNorm eps 1e-5.  About 3.19 B parameters.
"""
from .base import ModelConfig

# the published ``layer_types``: "attention" is the program's "dense" block
LAYER_TYPES = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4

CONFIG = ModelConfig(
    name="granite_4_0_h_micro", family="hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
    d_ff=8192, vocab_size=100352, rms_eps=1e-5, tie_embeddings=True,
    ssm_state=128, ssm_expand=2, ssm_headdim=64, ssm_conv_width=4,
    ssm_chunk=256,
    layer_kinds=tuple("dense" if t == "attention" else "mamba"
                      for t in LAYER_TYPES),
    rope=False,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8.0,
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
           "config.json",
)
