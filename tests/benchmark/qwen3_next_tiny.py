"""The ``qwen3-next-80b-a3b`` configuration and its ``longdoc`` mix at a
tiny size, for rehearsals on the CPU: the published file's shape (two
periods of linear x 3 + full, a stated head size that is not
hidden/heads, 8 experts top-2 of which 4 are held, a shared expert) with
every width shrunk. ``conftest.py`` writes them into the temporary
checkouts the runner tests build, under the real names."""

TINY_QWEN3_NEXT = {
    "block": "qwen3_next",
    "hidden_size": 32, "intermediate_size": 64, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 128, "max_position_embeddings": 512,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "num_experts": 4, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4,
    "transformer_config": {
        "vocab_size": 128, "hidden_size": 32, "intermediate_size": 64,
        "num_layers": 8, "num_heads": 4, "num_kv_heads": 2, "head_size": 16,
        "max_seq_len": 256, "norm": "rmsnorm", "norm_eps": 1e-06,
        "norm_zero_centered": True, "activation": "silu",
        "position": "rope", "rope_pct": 0.25, "rope_theta": 10000000,
        "parallel_residual": False, "tie_embeddings": False,
        "use_bias": False, "dtype": "float32",
        "layer_pattern": ["linear", "linear", "linear", "full"],
        "attn_output_gate": True, "qk_norm": True,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 8, "linear_value_head_dim": 8,
        "linear_conv_kernel": 4,
        "moe_num_experts": 8, "moe_top_k": 2, "moe_dropless": True,
        "moe_norm_topk": True, "moe_held_experts": [2, 4],
        "moe_intermediate_size": 16, "moe_shared_intermediate_size": 16},
    "engine": {"kv_block_size": 16, "kv_blocks": 128,
               "max_ragged_sequence_count": 4, "max_chunk_tokens": 32,
               "max_ragged_batch_size": 96, "compile_ahead": 2},
    "check": {"requests": 2, "decode_steps": 2, "max_prompt_tokens": 256,
              "tolerance": 1e-4, "rms_tolerance": 1e-4},
}
TINY_LONGDOC = {
    "generator": "stratified", "loop": "open",
    "prompt_tokens": {"median": 40, "sigma": 0.5, "min": 8, "max": 90},
    "output_tokens": {"median": 8, "sigma": 0.4, "min": 4, "max": 14},
    "schedule_seed": 0, "preroll_s": 0.5, "drain_s": 30}
