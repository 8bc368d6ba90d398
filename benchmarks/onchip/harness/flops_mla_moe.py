"""Operations and bytes that the work needs, from the sizes of a DeepSeek-V3
style decoder (the keys of a ``deepseek_v3_moe`` configuration's
``config``): multi-head latent attention, a dense FFN on the first
``first_k_dense_replace`` layers, then MoE layers of a router, shared
experts and the ``n_routed_experts`` routed experts held here.

As in ``flops.py`` these count what the algorithm requires: one forward
over each token, with attention in its standard (decompressed) form; the
routed experts count per assignment that lands on a held expert, which
the program counts itself.  Reused and decoded tokens count the same work.
"""
from __future__ import annotations

from typing import Tuple

from .flops import BF16, live_kv_tokens


def moe_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def attention_params(c: dict) -> int:
    """Matmul parameters of one MLA layer (no q compression)."""
    d, H, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    nd, rd, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    return d * H * (nd + rd) + d * (r + rd) + r * H * (nd + vd) + H * vd * d


def expert_params(c: dict) -> int:
    """One routed expert's SwiGLU."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_params(c: dict) -> int:
    """Matmul parameters every token passes through, whatever its routing:
    attention in every layer, the dense FFNs, each MoE layer's router and
    shared experts, and the output head over the vocabulary slice."""
    d = c["hidden_size"]
    per_moe = d * c["n_router_experts"] + c["n_shared_experts"] * expert_params(c)
    return (c["num_hidden_layers"] * attention_params(c)
            + c["first_k_dense_replace"] * 3 * d * c["intermediate_size"]
            + moe_layers(c) * per_moe + c["vocab_size"] * d)


def forward_flops(c: dict, seq_len: int, held_per_token: float) -> float:
    """One causal forward over ``seq_len`` tokens: 2 per matmul parameter
    per token, ``held_per_token`` routed assignments per token and MoE
    layer on held experts, and per (query, key <= query) pair and head
    2 (nope + rope) + 2 v FLOPs of standard-form attention."""
    T = int(seq_len)
    H, nd, rd, vd = (c["num_attention_heads"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"], c["v_head_dim"])
    attn = 2 * H * (nd + rd + vd) * T * (T + 1) // 2
    routed = 2.0 * expert_params(c) * held_per_token * moe_layers(c) * T
    return (2.0 * dense_params(c) * T + routed
            + c["num_hidden_layers"] * attn)


def latent_bytes_per_token(c: dict) -> int:
    """Cached latent and shared rope key of one token in every layer."""
    return c["num_hidden_layers"] * (c["kv_lora_rank"]
                                     + c["qk_rope_head_dim"]) * BF16


def mla_decode_attention_cost(c: dict, rows) -> Tuple[float, float]:
    """(flops, bytes) of absorbed latent decode attention over the active
    rows' live latents (``flops.live_kv_tokens`` of rows (context,
    generated)): per live token, layer and head 2 (r + rd) score FLOPs and
    2 r output FLOPs; bytes are the live latent and rope key once per step
    (all heads share them), with each step's q (r + rd per head) read and
    the output (r per head) written, bf16."""
    L, H = c["num_hidden_layers"], c["num_attention_heads"]
    r, rd = c["kv_lora_rank"], c["qk_rope_head_dim"]
    kv_tok = live_kv_tokens(rows)
    active_steps = sum(g for _, g in rows)
    flops = float(L) * H * 2 * (2 * r + rd) * kv_tok
    nbytes = latent_bytes_per_token(c) * kv_tok \
        + L * active_steps * H * (2 * r + rd) * BF16
    return flops, float(nbytes)
