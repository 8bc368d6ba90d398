"""Jit'd public wrapper for the RWKV6 wkv recurrence.

Accepts model-layout tensors (B, T, H, hd) and returns the same layout, so
`repro.models.rwkv` can call it directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels

from .kernel import wkv_pallas
from .ref import wkv_ref


@functools.partial(jax.jit, static_argnames=("impl", "block_t"))
def wkv(r, k, v, w, u, s0, *, impl: str = "auto", block_t: int = 256):
    """r/k/v/w: (B, T, H, hd); u: (H, hd); s0: (B, H, hd, hd).

    Returns (y (B, T, H, hd) f32, s_final (B, H, hd, hd) f32).
    impl: 'auto' (pallas on TPU, ref elsewhere) | 'pallas' | 'interpret' | 'ref'.
    """
    B, T, H, hd = r.shape
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    rb, kb, vb, wb = (to_bh(t.astype(jnp.float32)) for t in (r, k, v, w))
    s0b = s0.reshape(B * H, hd, hd).astype(jnp.float32)

    impl = kernels.resolve("rwkv6_wkv", impl, "ref")
    if impl == "ref":
        yb, sb = wkv_ref(rb, kb, vb, wb, u, s0b)
    else:
        yb, sb = wkv_pallas(rb, kb, vb, wb, u, s0b, block_t=block_t,
                            interpret=(impl == "interpret"))
    y = yb.reshape(B, H, T, hd).transpose(0, 2, 1, 3)
    return y, sb.reshape(B, H, hd, hd)
