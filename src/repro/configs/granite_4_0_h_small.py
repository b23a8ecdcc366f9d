"""Granite 4.0-H Small (32B-A9B). [hf:ibm-granite/granite-4.0-h-small]

40L d_model=4096, interleaved: 36 Mamba-2 mixers (128 heads of 64,
d_state 128, expand 2, one group, conv 4, chunk 256) and 4 GQA attention
layers (32 query / 8 KV heads of 128, no positional encoding, scale
``attention_multiplier`` 1/128) at indices 5, 15, 25, 35. Every layer ends
in an MoE of 72 experts of width 768, top-10, plus one shared SwiGLU
expert of width 1536. muP multipliers: embedding 12, residual 0.22, logits
/16. Tied vocabulary of 100352, RMSNorm eps 1e-5.

``CUT`` is the one-chip share of a 16-chip deployment (four pipeline
stages of 10 layers; each stage a 2x2 host whose chips split every layer's
experts 18 apiece): the first whole period of 10 layers, experts 0-17
held. Every width and the vocabulary are as published.
"""
import dataclasses

from .base import ModelConfig

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
LAYER_TYPES = PERIOD * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    layer_types=LAYER_TYPES,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100352,
    rope_theta=0.0,                      # position_embedding_type: nope
    attn_scale=0.0078125,                # attention_multiplier
    num_experts=72,
    top_k=10,
    moe_d_ff=768,
    num_shared_experts=1,
    shared_d_ff=1536,
    moe_impl="dropless",
    ssm_state=128,
    ssm_headdim=64,
    ssm_expand=2,
    ssm_groups=1,
    ssm_conv=4,
    ssm_chunk=256,
    tie_embeddings=True,
    embed_scale=12.0,
    residual_scale=0.22,
    logit_scale=1.0 / 16,
    norm_eps=1e-5,
    scan_layers=False,
    loss_chunk=2048,
)

CUT = dataclasses.replace(CONFIG, name="granite-4.0-h-small-cut",
                          num_layers=10, layer_types=PERIOD, experts_held=18)
