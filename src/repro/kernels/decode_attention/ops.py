"""Jit'd public wrapper for flash-decode attention.

``decode_attention`` is the short-query decode dual of
``kernels/flash_attention``: every decode step in ``generate``,
``resume_from_cache`` and the serving slot engine routes here (see
models/attention.py), as does the k+1-token draft-verify block of the
drafting engine (DESIGN.md §9).  ``lengths`` carries each row's live cache
extent (write offset + block width) and ``starts`` its first live slot
(dead left-padding in front of a compacted / left-padded context), letting
the blocked path iterate only live chunks and the Pallas kernel early-exit
per row.

``q_pos`` may be (B,) / (B, 1) (classic single-token decode) or (B, T) for
a T-token block.  The Pallas path additionally requires the block layout
every decode caller produces: per row, a valid prefix of queries at
consecutive positions (q_pos[b, t] == q_pos[b, 0] + t for t < q_len, -1
after) — the wrapper derives the (q_pos0, q_len) scalars the kernel
prefetches.  The ref/blocked oracles accept arbitrary per-query positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import (MLA_BLOCK_K, decode_attention_mla_pallas,
                     decode_attention_pallas, paged_decode_attention_pallas,
                     reads_in_place)
from .ref import (decode_attention_blocked, decode_attention_ref,
                  mla_decode_attention_ref)

# Below this cache width a single naive score pass beats the blocked
# while_loop's bookkeeping (one block_k=128 chunk covers it anyway).
NAIVE_MAX_S = 128


def _kernel_bounds(q_pos, lengths, starts, B: int, T: int, S: int):
    """The kernels' scalar-prefetch operands: (q_pos0, q_len, lengths,
    starts), each (B,) int32, with lengths/starts defaulted and clamped to
    [0, S]."""
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    lengths = jnp.minimum(lengths.reshape(B).astype(jnp.int32), S)
    if starts is None:
        starts = jnp.zeros((B,), jnp.int32)
    starts = jnp.clip(starts.reshape(B).astype(jnp.int32), 0, S)
    q_pos = q_pos.reshape(B, -1).astype(jnp.int32)
    if q_pos.shape != (B, T):
        # same rejection as ref._norm_inputs: a (B,)/(B, 1) position for a
        # T > 1 block would silently mean different things per impl
        raise ValueError(f"q_pos {q_pos.shape} must be (B, T)={B, T} for "
                         f"T > 1 query blocks")
    # valid-prefix query-block contract (see module docstring)
    q_len = jnp.sum((q_pos >= 0).astype(jnp.int32), axis=1)
    return q_pos[:, 0], q_len, lengths, starts


@functools.partial(jax.jit, static_argnames=("window", "impl", "block_k"))
def decode_attention(q, k, v, q_pos, k_pos, lengths=None, starts=None,
                     layer=None, *, window: int = 0, impl: str = "auto",
                     block_k: int = 128):
    """Short-query decode attention over a dense cache.

    q: (B, Hq, T, Dk) with small T (1 = classic decode, k+1 = draft-verify
    block); k: (B, Hkv, S, Dk); v: (B, Hkv, S, Dv) (Dk may differ from Dv —
    MLA); q_pos: (B,), (B, 1) or (B, T); k_pos: (B, S); lengths/starts:
    optional (B,) int32 live bounds — slot j of row b is attended only when
    starts[b] <= j < lengths[b] (None = [0, S)).  layer: optional scalar
    int32, for the kernel impls only — k/v are then a run's stacked cache
    (L, B, Hkv, S, D), read in place at that layer, and k_pos that layer's
    positions.  Returns (B, Hq, T, Dv) float32.

    impl: 'auto' (pallas on TPU; elsewhere naive for S <= NAIVE_MAX_S,
    length-bounded blocked beyond) | 'pallas' | 'interpret' | 'blocked' |
    'naive'.  ``block_k`` is the blocked path's chunk; the kernel chooses
    its tile from the shapes (``kernel.decode_block_k``).
    """
    S = k_pos.shape[1]
    if impl == "auto":
        if jax.default_backend() == "tpu":
            impl = "pallas"
        elif S <= NAIVE_MAX_S:
            impl = "naive"
        else:
            impl = "blocked"
    if impl == "naive":
        return decode_attention_ref(q, k, v, q_pos, k_pos, lengths, starts,
                                    window=window)
    if impl == "blocked":
        return decode_attention_blocked(q, k, v, q_pos, k_pos, lengths,
                                        starts, window=window,
                                        block_k=block_k)
    B, _, T = q.shape[:3]
    q_pos0, q_len, lengths, starts = _kernel_bounds(q_pos, lengths, starts,
                                                    B, T, S)
    return decode_attention_pallas(q, k, v, q_pos0, q_len, k_pos,
                                   lengths, starts, layer, window=window,
                                   interpret=(impl == "interpret"))


def gather_paged_kv(pool, table):
    """Materialise the logical dense view of a paged K/V pool.

    pool: (NB, Hkv, bs, D) (GQA) or (NB, bs, D) (MLA latents); table:
    (B, nb) int32.  Returns (B, Hkv, nb*bs, D) / (B, nb*bs, D) — the exact
    array a dense cache would hold at the same positions, which is what
    makes every dense attention path (naive / blocked / mesh shard_map) a
    valid paged fallback.  Under jit the gather is dead-code-eliminated
    whenever the paged kernel path is taken instead.
    """
    B, nb = table.shape
    g = jnp.take(pool, table.reshape(-1), axis=0)
    if pool.ndim == 4:
        NB, Hkv, bs, D = pool.shape
        return (g.reshape(B, nb, Hkv, bs, D).transpose(0, 2, 1, 3, 4)
                .reshape(B, Hkv, nb * bs, D))
    NB, bs, D = pool.shape
    return g.reshape(B, nb * bs, D)


@functools.partial(jax.jit, static_argnames=("window", "impl"))
def paged_decode_attention(q, k_pool, v_pool, table, q_pos, k_pos,
                           lengths=None, starts=None, layer=None, *,
                           window: int = 0, impl: str = "auto"):
    """Short-query decode attention over a paged cache (DESIGN.md §13).

    q: (B, Hq, T, Dk); k_pool/v_pool: (NB, Hkv, bs, D) physical block
    pools; table: (B, nb) int32 block table (logical slot j of row b lives
    at ``pool[table[b, j // bs], :, j % bs]``); k_pos: (B, nb*bs) dense
    positions; lengths/starts as in ``decode_attention``.  layer: optional
    scalar int32, for the kernel impls only — the pools are then a run's
    stacked pools (L, NB, Hkv, bs, D), read in place at that layer (table
    is that layer's).

    impl: 'pallas' | 'interpret' run the paged flash kernel (split axis ==
    block axis, table-redirected DMAs); 'naive' | 'blocked' | 'auto'-on-CPU
    gather the pool to its dense view and defer to ``decode_attention`` —
    bit-identical by construction, and the oracle the kernel is tested
    against.
    """
    B, _, T = q.shape[:3]
    bs = k_pool.shape[-2]
    S = table.shape[1] * bs
    if k_pos.shape[1] < S:
        # logical width short of the block-rounded physical width: the
        # rounding slack is empty by construction, so pad with -1 (masked)
        k_pos = jnp.pad(k_pos, ((0, 0), (0, S - k_pos.shape[1])),
                        constant_values=-1)
    if impl == "auto":
        if jax.default_backend() == "tpu":
            impl = "pallas"
        else:
            impl = "naive" if S <= NAIVE_MAX_S else "blocked"
    if impl in ("naive", "blocked"):
        k = gather_paged_kv(k_pool, table)
        v = gather_paged_kv(v_pool, table)
        return decode_attention(q, k, v, q_pos, k_pos, lengths, starts,
                                window=window, impl=impl, block_k=bs)
    q_pos0, q_len, lengths, starts = _kernel_bounds(q_pos, lengths, starts,
                                                    B, T, S)
    return paged_decode_attention_pallas(
        q, k_pool, v_pool, table, q_pos0, q_len, k_pos, lengths, starts,
        layer, window=window, interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("scale", "impl", "block_k"))
def decode_attention_mla(q_lat, q_rope, ckv, krope, q_pos, k_pos,
                         lengths=None, starts=None, layer=None, *,
                         scale: float, impl: str = "auto",
                         block_k: int = MLA_BLOCK_K):
    """Absorbed latent-attention decode over a dense latent cache.

    q_lat: (B, H, T, r) queries through the K half of ``wkv_b``; q_rope:
    (B, H, T, rd); ckv: (B, S, r); krope: (B, S, rd); q_pos/k_pos/lengths/
    starts as in ``decode_attention``.  layer: optional scalar int32, for
    the kernel impls only — ckv/krope are then a run's stacks (L, B, S, D),
    read in place at that layer.  Returns the weighted latent (B, H, T, r)
    float32.

    impl: 'pallas' | 'interpret' run the kernel (S a whole number of
    ``block_k`` tiles, T a valid-prefix query block); 'naive' the jnp
    absorbed form; 'auto' the kernel on TPU, else jnp."""
    S = k_pos.shape[1]
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "naive"
    if impl == "naive":
        return mla_decode_attention_ref(q_lat, q_rope, ckv, krope, q_pos,
                                        k_pos, lengths, starts, scale=scale)
    B, _, T = q_lat.shape[:3]
    q_pos0, q_len, lengths, starts = _kernel_bounds(q_pos, lengths, starts,
                                                    B, T, S)
    return decode_attention_mla_pallas(
        q_lat, q_rope, ckv, krope, q_pos0, q_len, k_pos, lengths,
        starts, layer, scale=scale, block_k=block_k,
        interpret=(impl == "interpret"))
