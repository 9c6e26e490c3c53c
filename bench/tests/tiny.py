"""Tiny configurations and traffic for the CPU tests of the benchmark."""

DEEPSEEK_LIKE = {
    "name": "tiny-deepseek", "kind": "moe_lm",
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "vocab_size": 500, "rope_theta": 10000,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "torch_dtype": "float32", "deployment": {"ep_ring": 8},
}

GRANITE_LIKE = {
    "name": "tiny-granite", "kind": "moe_lm",
    "hidden_size": 48, "intermediate_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_local_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "vocab_size": 300, "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "embedding_multiplier": 1.0,
    "attention_multiplier": 12 ** -0.5,
    "residual_multiplier": 1.0, "logits_scaling": 1.0,
    "torch_dtype": "float32", "deployment": {"ep_ring": 4},
}

TRAFFIC = {
    "driver": "serve", "loop": "closed", "batch": 4, "prompt_len": 8,
    "output_len": 6, "check_requests": 3,
    "trace": {"batch_index": 0,
              "slices": [{"prefill": True, "decode_steps": 5}]},
}
