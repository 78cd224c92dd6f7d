"""granite-4.0-h-micro — hybrid Mamba-2 / GQA decoder (granitemoehybrid).

[hf: ibm-granite/granite-4.0-h-micro, config.json]
40L d_model=2048 in four periods of ten: layers 5, 15, 25, 35 are GQA
attention (32 query / 8 KV heads of 64, no position embedding), the other
36 Mamba-2 mixers (d_inner 4096, 64 heads of 64, d_state 128, 1 group,
conv 4 with bias, chunk 256).  Every layer is followed by a SwiGLU MLP of
8192.  muP multipliers: embedding x12, residual branches x0.22, logits
/8, attention scores x1/64.  Head tied to the embedding, vocab 100352.
"""
from repro.models.config import ModelConfig, SSMConfig

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
    vocab_size=100352,
    tie_embeddings=True,
    norm_eps=1e-5,
    pos_emb="none",
    ssm=SSMConfig(state_dim=128, head_dim=64, expand=2, n_groups=1,
                  conv_width=4, chunk_size=256),
    layer_types=_PERIOD * 4,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    attention_multiplier=1.0 / 64,
    max_seq_len=131072,
    dtype="bfloat16",
)

REDUCED = ModelConfig(
    name="granite-4.0-h-micro-reduced",
    family="hybrid",
    num_layers=10,
    d_model=64,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    d_ff=128,
    vocab_size=512,
    tie_embeddings=True,
    norm_eps=1e-5,
    pos_emb="none",
    ssm=SSMConfig(state_dim=16, head_dim=16, expand=2, n_groups=1,
                  conv_width=4, chunk_size=16),
    layer_types=_PERIOD,
    # at width 64 the published x12 and x0.22 let a tied head's own token
    # dominate the logits of seeded random weights (the model repeats its
    # input whatever its state); every multiplier stays away from 1, so
    # that leaving one out still shows
    embedding_multiplier=1.5,
    residual_multiplier=0.5,
    logits_scaling=8.0,
    attention_multiplier=1.0 / 16,
    dtype="float32",
)
