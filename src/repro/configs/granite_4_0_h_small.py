"""granite-4.0-h-small [hybrid moe] — 40L d4096: 36 Mamba-2 layers
(128 heads x 64, d_state 128, one group, conv 4, chunk 256) and 4 GQA
attention layers (32 heads over 8 KV heads of 128, no position embedding),
every layer with an MoE of 72 experts of 768 top-10 and one shared SwiGLU
of 1536; vocab 100352 tied; GraniteMoE's embedding (12), residual (0.22),
attention (1/128) and logits (16) multipliers.
[hf:ibm-granite/granite-4.0-h-small, model_type granitemoehybrid]

The layer period is 10, attention at position 5 of each (layers 5, 15,
25, 35).  The shared SwiGLU of width 1536 is two 768-wide shared experts
on split columns.  Routing is a softmax over the top-10 router logits,
which equals the renormalised top-k softmax of ``moe.route``.
"""
from repro.models.config import LayerSpec, ModelConfig

ARCH_ID = "granite-4.0-h-small"

PERIOD = tuple(LayerSpec("attn" if i == 5 else "mamba2", "moe")
               for i in range(10))

CONFIG = ModelConfig(
    name=ARCH_ID,
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=768,                 # per expert
    vocab_size=100352,
    pattern=PERIOD,
    n_experts=72,
    top_k=10,
    n_shared_experts=2,
    d_ff_expert=768,
    nope=True,
    mamba_d_state=128,
    mamba_expand=2,
    mamba_d_conv=4,
    mamba_n_heads=128,
    mamba_head_dim=64,
    mamba_n_groups=1,
    mamba_chunk_size=256,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=1 / 128,
    logits_scaling=16.0,
    tie_embeddings=True,
    norm_eps=1e-5,
)
