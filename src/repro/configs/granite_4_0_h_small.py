"""Granite 4.0-H Small (32B total / 9B active) — a hybrid of Mamba-2 SSD
and NoPE GQA attention, 36:4 (attention at layers 5, 15, 25, 35), every
layer followed by a mixture of 72 SwiGLU experts of width 768 (top 10,
softmax over the chosen logits) plus one shared SwiGLU MLP of width 1536.
Embeddings ×12, each sublayer's output ×0.22 before its residual add,
attention scores ×1/128, logits ÷16, head tied.  The expert width is read
from ``intermediate_size``, an inference.
[https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json]"""

from .base import ModelConfig

_ATTENTION = (5, 15, 25, 35)

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=0,                  # no dense MLP: every layer's FFN is the MoE
    vocab_size=100352,
    norm_eps=1e-5,
    tie_embeddings=True,
    act="silu",
    layer_types=tuple("attention" if i in _ATTENTION else "mamba"
                      for i in range(40)),
    position_embedding="nope",
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=1 / 128,
    logits_scaling=16.0,
    n_experts=72,
    top_k=10,
    moe_d_ff=768,
    n_shared_experts=1,
    shared_d_ff=1536,
    ssm_state=128,
    ssm_expand=2,
    ssm_headdim=64,          # d_inner 8192 → 128 ssm heads
    ssm_ngroups=1,
    conv_kernel=4,
    ssd_chunk=256,
    param_dtype="bfloat16",  # as released
    citation="https://huggingface.co/ibm-granite/granite-4.0-h-small",
)
