"""A tiny DeepSeek-V3 MoE configuration (latent attention, a dense first
layer, 4 of 8 routed experts held from expert 2, 2 shared) for CPU tests
of the harness: the real program paths at a size a test run can hold."""
import onchip_tiny  # noqa: F401  (puts the harness on sys.path)

ENTRY = {
    "family": "deepseek_v3_moe",
    "config": {"first_k_dense_replace": 1, "hidden_size": 64,
               "intermediate_size": 128, "kv_lora_rank": 32,
               "moe_intermediate_size": 32, "moe_layer_freq": 1,
               "n_group": 1, "n_routed_experts": 4, "n_router_experts": 8,
               "first_held_expert": 2, "n_shared_experts": 2,
               "norm_topk_prob": True, "num_attention_heads": 4,
               "num_experts_per_tok": 3, "num_hidden_layers": 3,
               "num_key_value_heads": 4, "q_lora_rank": None,
               "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
               "rms_norm_eps": 1e-5, "rope_theta": 50000,
               "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
               "tie_word_embeddings": False, "topk_group": 1,
               "topk_method": "noaux_tc", "torch_dtype": "bfloat16",
               "v_head_dim": 16, "vocab_size": 512},
    "program": {"arch": "moonlight-16b-a3b", "overrides": {
        "num_layers": 3, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
        "d_ff": 128, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts": 8,
        "num_experts_per_tok": 3, "moe_d_ff": 32, "num_held_experts": 4,
        "held_expert_start": 2, "vocab_size": 512}},
    # at this size on the CPU the bf16 program reads 0.19-0.66 against the
    # float32 reference and the control (float8 matmul operands, bf16
    # activations) 1.06-1.64 (seeds 1, 2, 3, 11, 2**31 + 5, 2**31 + 11,
    # both mixes): routing flips on rounding dominate both; the limit lies
    # between
    "limits": {"lp_gap": 0.8},
}

CELL = {"name": "tiny-mla", "config": "tiny-mla", "chips": 1}
