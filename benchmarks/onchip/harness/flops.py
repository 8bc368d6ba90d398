"""Operations and bytes that the work needs, from the published sizes of a
dense GQA decoder (the keys of a ``configs/*.json`` ``config``).

These count what the algorithm requires, not what an implementation does:
one forward over each token, the weights read once per decode step, and the
K/V of rows that are still generating.  Reused and decoded tokens count the
same work.
"""
from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2


def matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix product per token, the output
    head included (tied or not)."""
    d, H, Hkv, hd, ff = (c["hidden_size"], c["num_attention_heads"],
                         c["num_key_value_heads"], c["head_dim"],
                         c["intermediate_size"])
    per_layer = d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * ff
    return c["num_hidden_layers"] * per_layer + c["vocab_size"] * d


def norm_params(c: dict) -> int:
    L, d, hd = c["num_hidden_layers"], c["hidden_size"], c["head_dim"]
    return L * (2 * d + 2 * hd) + d


def forward_flops(c: dict, seq_len: int) -> float:
    """One causal forward over ``seq_len`` tokens: 2 per matmul parameter per
    token, plus q.k and p.v over every (query, key <= query) pair."""
    T = int(seq_len)
    attn = 4 * c["num_attention_heads"] * c["head_dim"] * T * (T + 1) // 2
    return 2.0 * matmul_params(c) * T + c["num_hidden_layers"] * attn


def kv_bytes_per_token(c: dict) -> int:
    """K and V of one token in every layer, bf16."""
    return c["num_hidden_layers"] * 2 * c["num_key_value_heads"] \
        * c["head_dim"] * BF16


def live_kv_tokens(rows: Iterable[Tuple[int, int]]) -> int:
    """Sum over decode steps of the live context of the rows generating.

    rows: (context, generated) per row: ``context`` tokens are in the cache
    before the first decode step, and the row is active for ``generated``
    steps; at its step s it attends over context + s + 1 tokens."""
    return sum(g * ctx + g * (g + 1) // 2 for ctx, g in rows)


def decode_bytes(c: dict, steps: int, rows) -> float:
    """Least bytes of ``steps`` decode steps: every weight once per step,
    plus the live K/V of the active rows."""
    weights = BF16 * (matmul_params(c) + norm_params(c))
    return float(steps) * weights + kv_bytes_per_token(c) * live_kv_tokens(rows)


def decode_attention_cost(c: dict, rows) -> Tuple[float, float]:
    """(flops, bytes) of decode attention over the active rows' live K/V,
    with each step's q read and output written (bf16)."""
    L, H, hd = c["num_hidden_layers"], c["num_attention_heads"], c["head_dim"]
    kv_tok = live_kv_tokens(rows)
    active_steps = sum(g for _, g in rows)
    flops = float(L) * 4 * H * hd * kv_tok
    nbytes = kv_bytes_per_token(c) * kv_tok + L * active_steps * 2 * H * hd * BF16
    return flops, float(nbytes)
