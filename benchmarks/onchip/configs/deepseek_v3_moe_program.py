"""How the program under test runs a ``deepseek_v3_moe`` configuration: the
program's ModelConfig for it, and the benchmark's weights laid out as the
program's parameter tree.  Imports the program; the reference beside this
file does not."""
from __future__ import annotations

import jax

# config key -> the program's ModelConfig field
FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff", "moe_intermediate_size": "moe_d_ff",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "num_experts_per_tok": "num_experts_per_tok",
    "n_shared_experts": "num_shared_experts",
    "first_k_dense_replace": "first_dense_layers", "moe_layer_freq": "moe_every",
    "n_router_experts": "num_experts", "n_routed_experts": "held_experts",
    "first_held_expert": "held_expert_start", "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "routed_scaling_factor": "routed_scaling",
    "tie_word_embeddings": "tie_embeddings",
}


def model_config(entry: dict):
    """The program's registry config for ``entry["program"]["arch"]`` with
    its overrides, checked against the configuration's sizes."""
    from repro.configs import get_config
    prog = entry["program"]
    cfg = get_config(prog["arch"]).replace(**prog.get("overrides", {}))
    cfg.validate()
    return check_matches(cfg, entry["config"])


def check_matches(cfg, config: dict):
    for key, field in FIELDS.items():
        got, want = getattr(cfg, field), config[key]
        if (float(got) if isinstance(want, float) else got) != want:
            raise ValueError(f"program {field}={got} but the config says "
                             f"{key}={want}")
    if cfg.dtype != config["torch_dtype"] or cfg.param_dtype != config["torch_dtype"]:
        raise ValueError(f"program runs {cfg.dtype}/{cfg.param_dtype}, the "
                         f"config states {config['torch_dtype']}")
    if not (cfg.attention_kind == "mla" and cfg.q_lora_rank == 0
            and config["q_lora_rank"] is None
            and cfg.router_score == config["scoring_func"] == "sigmoid"
            and config["topk_method"] == "noaux_tc"
            and config["n_group"] == config["topk_group"] == 1
            and config["norm_topk_prob"] and cfg.ffn_kind == "swiglu"):
        raise ValueError("program config is not a DeepSeek-V3 decoder")
    return cfg


def _block(run: dict) -> dict:
    attn = {"wq": {"kernel": run["wq"]}, "wkv_a": {"kernel": run["wkv_a"]},
            "kv_norm": {"scale": run["kv_norm"]},
            "wkv_b": {"kernel": run["wkv_b"]}, "wo": {"kernel": run["wo"]}}
    return {"norm1": {"scale": run["attn_norm"]},
            "norm2": {"scale": run["mlp_norm"]}, "attn": attn}


def to_program_params(w):
    """Canonical weights (configs/deepseek_v3_moe.py) -> the program's tree:
    a scanned run of dense-FFN blocks, then one of MoE blocks.  The leaves
    are the same device arrays, not copies."""
    d, m = w["dense"], w["moe"]
    dense = dict(_block(d), mlp={"w_gate": {"kernel": d["w_gate"]},
                                 "w_up": {"kernel": d["w_up"]},
                                 "w_down": {"kernel": d["w_down"]}})
    moe = dict(_block(m), moe={
        "router": {"kernel": m["router"]},
        "e_score_correction_bias": m["e_score_correction_bias"],
        "w_gate": m["w_gate"], "w_up": m["w_up"], "w_down": m["w_down"],
        "shared": {"w_gate": {"kernel": m["shared_gate"]},
                   "w_up": {"kernel": m["shared_up"]},
                   "w_down": {"kernel": m["shared_down"]}}})
    trunk = [dense, moe] if d["wq"].shape[0] else [moe]
    return {"embed": w["embed"], "trunk": trunk,
            "final_norm": {"scale": w["final_norm"]},
            "lm_head": {"kernel": w["lm_head"]}}


def check_tree(cfg, params) -> None:
    """The laid-out tree has exactly the program's structure and shapes."""
    from repro.models import model as M
    want = jax.eval_shape(lambda k: M.init_lm(k, cfg),
                          jax.random.PRNGKey(0))
    got_s = jax.tree.structure(params)
    if got_s != jax.tree.structure(want):
        raise ValueError(f"parameter tree differs from the program's: "
                         f"{got_s} vs {jax.tree.structure(want)}")
    for g, w_ in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        if g.shape != w_.shape or g.dtype != w_.dtype:
            raise ValueError(f"leaf {g.shape}/{g.dtype} vs {w_.shape}/{w_.dtype}")
