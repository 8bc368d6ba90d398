"""moonlight-16b-a3b [moe]: DeepSeek-V3 blocks at 27L, d_model 2048, 16
heads of MLA (no q compression, kv latent 512, nope 128 + rope 64, v 128),
a dense first layer (d_ff 11264), then 64 routed experts of width 1408,
top-6 on sigmoid scores plus a correction bias (noaux_tc, one group),
normalised and scaled by 2.446, beside 2 shared experts; rope_theta 50000,
rms eps 1e-5, vocab 163840, untied
[hf:moonshotai/Moonlight-16B-A3B config.json]."""
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b", arch_type="moe",
        source="hf:moonshotai/Moonlight-16B-A3B",
        num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
        d_ff=11264, vocab_size=163840, max_seq_len=8192, norm_eps=1e-5,
        attention_kind="mla", q_lora_rank=0, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_experts=64, num_experts_per_tok=6, num_shared_experts=2,
        moe_d_ff=1408, first_dense_layers=1, moe_every=1,
        router_score="sigmoid", routed_scaling=2.446,
        rope_theta=50_000.0,
        dtype="bfloat16", param_dtype="bfloat16",
    )
