"""A tiny Qwen3-dense configuration and traffic for CPU tests of the
harness: the real program paths at a size a test run can hold."""
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

ENTRY = {
    "family": "qwen3_dense",
    "config": {"head_dim": 16, "hidden_size": 64, "intermediate_size": 128,
               "num_attention_heads": 4, "num_hidden_layers": 2,
               "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
               "rope_theta": 1e6, "tie_word_embeddings": True,
               "torch_dtype": "bfloat16", "vocab_size": 512},
    "program": {"arch": "qwen3-0.6b", "overrides": {
        "num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab_size": 512}},
    # at this size on the CPU the bf16 program reads 0.004-0.007 against
    # the float32 reference and the float8 control 0.057-0.10 (seeds 1, 2,
    # 3, 2**31 + 5, both mixes); the limit lies between
    "limits": {"lp_gap": 0.025},
}

REUSE = {"group_size": 4,
         "prompt_len": {"min": 6, "max": 16, "pad_to": 16, "cycle": 4},
         "max_new_tokens": 32, "temperature": 1.0, "top_p": 1.0, "spec": {},
         "previous_epoch": {"full_reuse_lengths": [10, 30],
                            "reject_positions": [5, 20]},
         "check_rows": 6}

FRESH = dict(REUSE, previous_epoch=None)

CELL = {"name": "tiny", "config": "tiny", "chips": 1}
