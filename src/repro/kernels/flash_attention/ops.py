"""Jit'd public wrapper for flash attention."""
from __future__ import annotations

import functools

import jax

from repro import kernels

from .kernel import flash_attention_pallas
from .ref import flash_attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "impl",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, q_pos, k_pos, *, causal: bool = True,
                    window: int = 0, impl: str = "auto",
                    block_q: int = 128, block_k: int = 128):
    """impl: 'auto' (pallas on TPU, ref elsewhere) | 'pallas' | 'interpret' | 'ref'."""
    # T == 1 would give a degenerate block_q=1 (single-MXU-row) schedule;
    # decode-shaped calls belong to kernels/decode_attention instead.
    assert q.shape[2] > 1, (
        "flash_attention is the prefill/verify kernel; single-token decode "
        f"(T={q.shape[2]}) must route to kernels/decode_attention")
    impl = kernels.resolve("flash_attention", impl, "ref")
    if impl == "ref":
        return flash_attention_ref(q, k, v, q_pos, k_pos, causal=causal,
                                   window=window)
    return flash_attention_pallas(q, k, v, q_pos, k_pos, causal=causal,
                                  window=window, block_q=block_q,
                                  block_k=block_k,
                                  interpret=(impl == "interpret"))
