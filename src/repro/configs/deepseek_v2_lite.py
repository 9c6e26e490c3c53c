"""deepseek-v2-lite [moe + latent attention] — 27L d2048, 16 heads of
multi-head latent attention (kv_lora_rank 512, q uncompressed, qk 128 nope
+ 64 rope, v 128; YaRN rotary x40 over 4096), vocab 102400, 2 shared + 64
routed experts of 1408 top-6, first layer dense (d_ff 10944).
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite]

Its MoE block is deepseek-moe-16b's key for key; what differs is the
attention, whose cache holds one 576-wide latent row per token and layer
for all heads (7.1x less than deepseek-moe-16b's MHA).
"""
from repro.models.config import LayerSpec, ModelConfig, YarnScaling

ARCH_ID = "deepseek-v2-lite"

CONFIG = ModelConfig(
    name=ARCH_ID,
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,                # per routed expert
    vocab_size=102400,
    pattern=(LayerSpec("mla", "moe"),),
    n_experts=64,
    top_k=6,
    n_shared_experts=2,
    d_ff_expert=1408,
    first_layer_dense=True,
    first_dense_d_ff=10944,
    rope_theta=10000.0,
    rope_scaling=YarnScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    norm_eps=1e-6,
)
