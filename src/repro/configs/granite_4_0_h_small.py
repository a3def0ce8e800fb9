"""granite-4.0-h-small [hybrid] — hf:ibm-granite/granite-4.0-h-small.

IBM Granite 4.0-H Small (32B-A9B): 40 layers, d_model 4096, a period of
ten (five Mamba-2, one attention, four Mamba-2), so attention sits at
layers 5, 15, 25 and 35.  Mamba-2 mixers of 128 heads of 64, d_state
128, one group, conv width 4, expand 2, chunk 256.  GQA attention with
32 query and 8 key/value heads of 128 and no positional encoding
(``position_embedding_type: nope``), scores scaled by 1/128.  Every
layer's feed-forward is a mixture of 72 SwiGLU experts of width 768,
top-10, plus one shared SwiGLU expert of width 1536.  muP multipliers:
embeddings x12, each residual branch x0.22, logits /16.  Tied
vocabulary of 100,352, RMSNorm eps 1e-5, weights published in bf16.

The published Mamba-2 convolution has a bias; this model has none.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=0,                    # every layer's FFN is the MoE block
    vocab_size=100352,
    layer_pattern=("mamba",) * 5 + ("attn",) + ("mamba",) * 4,
    rope=False,
    attn_scale=0.0078125,
    moe_num_experts=72,
    moe_top_k=10,
    moe_d_ff=768,
    moe_shared_d_ff=1536,
    moe_layer_period=1,
    ssm_state=128,
    ssm_headdim=64,
    ssm_expand=2,
    ssm_conv_width=4,
    ssm_chunk=256,
    norm_eps=1e-5,
    tie_embeddings=True,
    param_dtype="bfloat16",
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
)
