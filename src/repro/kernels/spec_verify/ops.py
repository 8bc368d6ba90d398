"""Jit'd public wrapper for the spec_verify kernel.

``spec_verify`` returns, per batch row, the first rejected draft position
``n`` ∈ [0, valid_len] (== valid_len ⇒ full acceptance): the Pallas kernel
on TPU, its oracle elsewhere (``repro.kernels.resolve``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels

from .kernel import INT_MAX, spec_verify_pallas
from .ref import spec_verify_ref


@functools.partial(jax.jit, static_argnames=("impl", "block_b", "block_t"))
def spec_verify(lp_curr, lp_prev, u, valid_len, log_lenience, *,
                impl: str = "auto", block_b: int = 8, block_t: int = 512):
    """impl: 'auto' (pallas on TPU, ref elsewhere) | 'pallas' | 'interpret' | 'ref'."""
    impl = kernels.resolve("spec_verify", impl, "ref")
    if impl == "ref":
        return spec_verify_ref(lp_curr, lp_prev, u, valid_len, log_lenience)
    raw = spec_verify_pallas(lp_curr, lp_prev, u, valid_len, log_lenience,
                             block_b=block_b, block_t=block_t,
                             interpret=(impl == "interpret"))
    return jnp.minimum(raw, valid_len.astype(jnp.int32))
