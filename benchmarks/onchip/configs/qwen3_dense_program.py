"""How the program under test runs a ``qwen3_dense`` configuration: the
program's ModelConfig for it, and the benchmark's weights laid out as the
program's parameter tree.  Imports the program; the reference beside this
file does not."""
from __future__ import annotations

import jax

# published config key -> the program's ModelConfig field
FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
}


def model_config(entry: dict):
    """The program's registry config for ``entry["program"]["arch"]`` with
    its overrides, checked against the published sizes."""
    from repro.configs import get_config
    prog = entry["program"]
    cfg = get_config(prog["arch"]).replace(**prog.get("overrides", {}))
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
    if not (cfg.qk_norm and cfg.attention_kind == "gqa" and not cfg.qkv_bias
            and cfg.ffn_kind == "swiglu" and cfg.num_experts == 0):
        raise ValueError("program config is not a Qwen3 dense decoder")
    return cfg


def to_program_params(w):
    """Canonical weights (configs/qwen3_dense.py) -> the program's tree:
    one scanned run of identical attention blocks.  The leaves are the
    same device arrays, not copies."""
    block = {
        "norm1": {"scale": w["attn_norm"]},
        "norm2": {"scale": w["mlp_norm"]},
        "attn": {"wq": {"kernel": w["wq"]}, "wk": {"kernel": w["wk"]},
                 "wv": {"kernel": w["wv"]}, "wo": {"kernel": w["wo"]},
                 "q_norm": {"scale": w["q_norm"]},
                 "k_norm": {"scale": w["k_norm"]}},
        "mlp": {"w_gate": {"kernel": w["w_gate"]},
                "w_up": {"kernel": w["w_up"]},
                "w_down": {"kernel": w["w_down"]}},
    }
    params = {"embed": w["embed"], "trunk": [block],
              "final_norm": {"scale": w["final_norm"]}}
    if "lm_head" in w:
        params["lm_head"] = {"kernel": w["lm_head"]}
    return params


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
