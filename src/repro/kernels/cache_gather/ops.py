"""Jit'd public wrapper for the cache_gather compaction kernel.

``cache_roll`` right-rotates each (S, D) row of a flattened KV-cache buffer
by a per-row shift — the primitive behind model.realign_decode_cache, which
left-aligns verified [prompt | draft[:n]] context for cache-resumed decoding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels

from .kernel import cache_roll_pallas, paged_gather_pallas
from .ref import cache_roll_ref


@functools.partial(jax.jit, static_argnames=("impl",))
def cache_roll(buf, shift, *, impl: str = "auto"):
    """buf: (R, S, D); shift: (R,) int32 in [0, S].

    Returns out[r, j] = buf[r, (j - shift[r]) mod S].
    impl: 'auto' (pallas on TPU, ref elsewhere) | 'pallas' | 'interpret' | 'ref'.
    """
    assert buf.ndim == 3, buf.shape
    impl = kernels.resolve("cache_gather", impl, "ref")
    if impl == "ref":
        return cache_roll_ref(buf, shift)
    return cache_roll_pallas(buf, shift, interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl",))
def paged_gather(pool, table, *, impl: str = "auto"):
    """pool: (NB, X, D); table: (R, nb) int32 in [0, NB).

    Returns (R, nb, X, D) with out[r, i] = pool[table[r, i]] — the paged
    counterpart of this module's compaction primitive (DESIGN.md §13): it
    materialises the dense logical view of a block pool, which the paged
    realign path rolls with ``cache_roll`` before re-paging.
    impl: 'auto' (pallas on TPU, ref elsewhere) | 'pallas' | 'interpret' | 'ref'.
    """
    assert pool.ndim == 3 and table.ndim == 2, (pool.shape, table.shape)
    impl = kernels.resolve("cache_gather", impl, "ref")
    if impl == "ref":
        R, nb = table.shape
        return jnp.take(pool, table.reshape(-1), axis=0).reshape(
            R, nb, *pool.shape[1:])
    return paged_gather_pallas(pool, table, interpret=(impl == "interpret"))
