"""A tiny latent-attention configuration for the CPU tests of the
benchmark: DeepSeek-V2's keys and YaRN scaling, 4 of 8 routed experts
held, float32."""

MLA_LIKE = {
    "name": "tiny-mla", "kind": "mla_moe_lm",
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_scaling": {"type": "yarn", "factor": 40,
                     "original_max_position_embeddings": 4096,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707},
    "n_routed_experts": 4, "published": {"n_routed_experts": 8},
    "n_shared_experts": 1, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "vocab_size": 500, "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "torch_dtype": "float32",
}
