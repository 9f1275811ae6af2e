"""granite-4.0-h-micro [hybrid]: 40L d_model=2048, Mamba-2 in 36 layers and
NoPE GQA attention (32H, kv=8) in 4 (layers 5, 15, 25, 35), a SwiGLU MLP
of 8192 in every layer, vocab=100352, tied embeddings.
[hf: ibm-granite/granite-4.0-h-micro, config.json]

The Mamba-2 mixer: 64 heads of 64, state 128, one group, conv 4 (with
bias, no projection bias). Granite's muP multipliers: embeddings x12,
softmax scale 1/64 (not 1/sqrt(64)), both residual branches x0.22,
logits / 8. Attention carries no position embedding.
"""

from ..models.config import ModelConfig

ATTENTION_LAYERS = (5, 15, 25, 35)

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    layer_types=tuple("attention" if i in ATTENTION_LAYERS else "mamba"
                      for i in range(40)),
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100352,
    act="swiglu",
    norm_eps=1e-5,
    position_embedding="nope",
    tie_embeddings=True,
    ssm_state=128,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_chunk=256,
    conv_kernel=4,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
)
