"""Jit'd public wrapper for the cache_slot_write admission kernel.

``cache_slot_write`` replaces selected rows of a flattened KV-cache buffer
with freshly prefilled source rows — the primitive behind
model.write_cache_slots, which admits new requests into the persistent
serving batch by in-place slot replacement (DESIGN.md §6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels

from .kernel import cache_slot_write_pallas
from .ref import cache_slot_write_ref


def _invert_rows(dst_rows, n_dst: int, n_src: int):
    """dst_rows: (Rs,) -> src_for_dst: (Rd,) with -1 for untouched rows.

    Deterministic on duplicates: the LAST source row targeting a
    destination wins (the admission path only ever duplicates identical
    rows, but the contract should not depend on scatter ordering).
    scatter-max over source indices IS last-wins — "last" = highest index —
    and stays O(Rd + Rs) on the admission hot path.
    """
    return jnp.full((n_dst,), -1, jnp.int32).at[dst_rows].max(
        jnp.arange(n_src, dtype=jnp.int32), mode="drop")


@functools.partial(jax.jit, static_argnames=("impl",))
def cache_slot_write(dst, src, dst_rows, *, impl: str = "auto"):
    """dst: (Rd, S, D); src: (Rs, S, D); dst_rows: (Rs,) int32 in [0, Rd).

    Returns out with out[dst_rows[i]] = src[i] and every other destination
    row unchanged.  Duplicate dst_rows: the last source row wins.
    impl: 'auto' (pallas on TPU, ref elsewhere) | 'pallas' | 'interpret' | 'ref'.
    """
    assert dst.ndim == 3 and src.ndim == 3, (dst.shape, src.shape)
    assert dst.shape[1:] == src.shape[1:], (dst.shape, src.shape)
    src_for_dst = _invert_rows(dst_rows.astype(jnp.int32), dst.shape[0],
                               src.shape[0])
    impl = kernels.resolve("cache_slot_write", impl, "ref")
    if impl == "ref":
        return cache_slot_write_ref(dst, src, src_for_dst)
    return cache_slot_write_pallas(dst, src, src_for_dst,
                                   interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl",))
def paged_slot_write(pool, src, tables, *, impl: str = "auto"):
    """Paged admission counterpart of ``cache_slot_write`` (DESIGN.md §13).

    pool: (run, NB, Hkv, bs, D) or (run, NB, bs, D) physical block pool;
    src: (run, R, Hkv, S, D) / (run, R, S, D) dense admitted rows with
    S == nb * bs; tables: (run, R, nb) int32 — the block-table rows of the
    admitted slots.  Each dense source row is cut into nb logical blocks
    and scattered to the physical blocks its table references; every other
    pool block is untouched.

    The scatter itself reuses the ``cache_slot_write`` dest-walking kernel:
    physical blocks are flattened to (run*NB, Hkv*bs, D) rows and the
    block ids become destination-row indices, so the Pallas path gets the
    same redirect-the-DMA schedule admission already uses for dense slots.
    """
    run_len, NB = pool.shape[:2]
    bs, D = pool.shape[-2], pool.shape[-1]
    nb = tables.shape[-1]
    R = tables.shape[1]
    assert tables.shape == (run_len, R, nb), tables.shape
    if pool.ndim == 5:
        Hkv = pool.shape[2]
        blocks = (src.reshape(run_len, R, Hkv, nb, bs, D)
                  .transpose(0, 1, 3, 2, 4, 5)
                  .reshape(run_len * R * nb, Hkv * bs, D))
        flat_pool = pool.reshape(run_len * NB, Hkv * bs, D)
    else:
        blocks = src.reshape(run_len * R * nb, bs, D)
        flat_pool = pool.reshape(run_len * NB, bs, D)
    r0 = jnp.arange(run_len, dtype=jnp.int32)[:, None, None]
    rows = (r0 * NB + tables.astype(jnp.int32)).reshape(-1)
    out = cache_slot_write(flat_pool, blocks.astype(pool.dtype), rows,
                           impl=impl)
    return out.reshape(pool.shape)
