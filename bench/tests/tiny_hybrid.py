"""A tiny hybrid Mamba-2 / attention configuration for the CPU tests of
the benchmark: GraniteMoeHybrid's keys and multipliers, two groups of
Mamba-2 heads, 4 of 8 routed experts held, float32."""

HYBRID_LIKE = {
    "name": "tiny-hybrid", "kind": "hybrid_moe_lm",
    "hidden_size": 64, "intermediate_size": 32,
    "shared_intermediate_size": 64, "num_hidden_layers": 4,
    "layer_types": ["mamba", "attention"] * 3,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_bias": False, "position_embedding_type": "nope",
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "num_local_experts": 4, "published": {"num_local_experts": 8},
    "num_experts_per_tok": 3, "vocab_size": 500, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "embedding_multiplier": 12,
    "attention_multiplier": 0.0078125, "residual_multiplier": 0.22,
    "logits_scaling": 16, "torch_dtype": "float32",
}
