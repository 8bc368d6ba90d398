"""Attention: GQA/MQA/MHA with qk-norm, qkv-bias, RoPE, sliding window,
cross-attention, and DeepSeek-V3 MLA (multi-head latent attention).

Position-based masking
----------------------
Every token carries an explicit integer position; padding slots carry -1.
A query at position ``pq`` may attend to a key at position ``pk`` iff::

    pk >= 0  and  pk <= pq          (causal)
    and pq - pk < window            (if sliding window > 0)

This one rule serves training, left-padded prefill and single-token decode,
so prefill+decode is provably equivalent to a full forward (tested).

KV caches come in two layouts (``cfg.cache_layout``, DESIGN.md §13):

* **dense** (default): ``(B, Hkv, S, D)`` buffers plus a ``pos`` array
  (B, S) holding each slot's position (-1 = empty).
* **paged**: physical block pools ``(NB, Hkv, bs, D)`` plus an int32 block
  ``table`` (B, nb) mapping logical block → physical block (logical slot j
  of row b lives at ``pool[table[b, j // bs], :, j % bs]``).  ``pos`` stays
  dense, so position-based masking — and therefore every output — is
  untouched by the layout; physical block 0 is a reserved garbage sink
  (serving/block_table.py).  Both layouts stay statically shaped, which is
  what XLA/TPU wants; paging only redirects which tiles the decode kernel
  DMAs.

Inside the trunk (models/blocks.py) each cache leaf is a run's *stack*, one
layer per leading index, carried through the layer scan: a layer writes its
new K/V into the stack at ``[layer, ..., cache_start]`` in place, and the
flash-decode kernel reads its layer straight out of the stack (DESIGN.md
§3).  Every other read takes that layer's slice.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import (apply_dense, apply_rmsnorm, apply_rope, make_dense,
                     make_rmsnorm, split_keys)

NEG_INF = -1e30


# ------------------------------------------------------------------ core math


def dot_product_attention(q, k, v, q_pos, k_pos, *, window: int = 0,
                          causal: bool = True, impl: str = "naive",
                          block_k: int = 1024) -> jnp.ndarray:
    """Grouped-query attention with position-based masking.

    q: (B, Hq, T, D); k/v: (B, Hkv, S, D); q_pos: (B, T); k_pos: (B, S).
    impl='blocked' streams KV chunks through an online softmax (flash
    attention expressed in XLA) so the (T, S) score matrix is never
    materialised — the pure-JAX analogue of kernels/flash_attention.
    """
    if impl == "blocked" and k.shape[2] > block_k:
        return _blocked_attention(q, k, v, q_pos, k_pos, window=window,
                                  causal=causal, block_k=block_k)
    B, Hq, T, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, T, D)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    scores = jnp.einsum("bhgtd,bhsd->bhgts", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = k_pos[:, None, None, None, :] >= 0
    if causal:
        mask &= k_pos[:, None, None, None, :] <= q_pos[:, None, None, :, None]
    if window > 0:
        mask &= (q_pos[:, None, None, :, None] - k_pos[:, None, None, None, :]) < window
    # Rows whose query is padding produce garbage that is masked downstream.
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    # Fully-masked rows: softmax of all -inf -> uniform garbage; zero them.
    any_valid = jnp.any(mask, axis=-1, keepdims=True)
    w = jnp.where(any_valid, w, 0.0)
    out = jnp.einsum("bhgts,bhsd->bhgtd", w, v.astype(jnp.float32))
    return out.reshape(B, Hq, T, v.shape[-1])


def _blocked_attention(q, k, v, q_pos, k_pos, *, window: int, causal: bool,
                       block_k: int) -> jnp.ndarray:
    """Online-softmax attention over KV chunks (peak memory ~ (T, block_k))."""
    B, Hq, T, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = Hq // Hkv
    pad = (-S) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    nch = k.shape[2] // block_k
    qg = q.reshape(B, Hkv, G, T, D).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    kc = jnp.moveaxis(k.reshape(B, Hkv, nch, block_k, D), 2, 0)
    vc = jnp.moveaxis(v.reshape(B, Hkv, nch, block_k, Dv), 2, 0)
    pc = jnp.moveaxis(k_pos.reshape(B, nch, block_k), 1, 0)

    def body(carry, xs):
        m, l, acc = carry                                   # (B,Hkv,G,T,1/Dv)
        k_b, v_b, p_b = xs
        s = jnp.einsum("bhgtd,bhsd->bhgts", qg,
                       k_b.astype(jnp.float32)) * scale
        mask = p_b[:, None, None, None, :] >= 0
        if causal:
            mask &= p_b[:, None, None, None, :] <= \
                q_pos[:, None, None, :, None]
        if window > 0:
            mask &= (q_pos[:, None, None, :, None]
                     - p_b[:, None, None, None, :]) < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = corr * acc + jnp.einsum("bhgts,bhsd->bhgtd", p,
                                      v_b.astype(jnp.float32))
        return (m_new, l, acc), None

    init = (jnp.full((B, Hkv, G, T, 1), NEG_INF, jnp.float32),
            jnp.zeros((B, Hkv, G, T, 1), jnp.float32),
            jnp.zeros((B, Hkv, G, T, Dv), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, (kc, vc, pc))
    out = acc / jnp.where(l > 0, l, 1.0)
    return out.reshape(B, Hq, T, Dv)


# largest query block the decode-shaped path accepts (k + 1 for draft
# blocks); bigger cached-T calls take the prefill-style full-S paths
DECODE_BLOCK_MAX_T = 64


def _decode_shaped(cache, kv_x, causal, T: int, kv_length) -> bool:
    """Whether a cached call routes to the flash-decode op: single-token
    decode always; a short multi-token block (the §9 draft-verify forward)
    only when the caller threads its per-row live bounds explicitly."""
    if cache is None or kv_x is not None or not causal:
        return False
    return T == 1 or (kv_length is not None and T <= DECODE_BLOCK_MAX_T)


def _layer(stack, layer):
    """Layer ``layer`` of a stacked cache leaf: one dynamic slice."""
    return jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)


def _layer_view(stack, layer, table, width: int):
    """One layer's dense (B, [Hkv,] width, D) view of a stacked K/V leaf:
    its slice, or for paged pools (``table`` = the layer's block table) the
    gather of its blocks."""
    buf = _layer(stack, layer)
    return buf if table is None else _paged_gather(buf, table, width)


def _live_bounds(B: int, T: int, cache_start, kv_length, kv_start):
    """Per-row (lengths, starts) of a decode-shaped call: ``kv_length``,
    else ``cache_start + T`` (the just-written block ends the live range);
    ``kv_start`` or None (start at 0)."""
    if kv_length is None:
        kv_length = jnp.asarray(cache_start, jnp.int32) + T
    lengths = jnp.broadcast_to(
        jnp.asarray(kv_length, jnp.int32).reshape(-1), (B,))
    starts = None if kv_start is None else jnp.broadcast_to(
        jnp.asarray(kv_start, jnp.int32).reshape(-1), (B,))
    return lengths, starts


def _decode_attention(cfg: ModelConfig, q, k, v, q_pos, kv_pos, *,
                      window: int, cache_start, kv_length, kv_start, layer,
                      mesh=None, table=None) -> jnp.ndarray:
    """Route a decode-shaped (short-T, cached) call to the flash-decode op.

    ``kv_length`` is the per-row live cache extent.  When the caller does
    not thread it explicitly it is derived from ``cache_start``: the T
    decode tokens were just written at slots [cache_start, cache_start+T),
    so every slot at or beyond ``cache_start + T`` is empty (pos == -1) and
    can be skipped.  ``kv_start`` is the per-row first live slot (the dead
    left-padding in front of a left-padded / compacted context); only
    callers that know their layout is contiguous from that slot may thread
    it — None means start at 0, which is always safe.

    k/v are the run's stacked cache (L, B, Hkv, S, D), or with ``table``
    (this layer's block table) its stacked paged pools, and ``layer`` this
    layer's index.  ``decode_plan`` (kernels/decode_attention/ops.py)
    picks the implementation and whether the kernel reads the layer in
    place; every other path reads the layer's slice (or gathered view).

    ``mesh`` routes the call through the shard_map boundary (DESIGN.md §8):
    each device runs the kernel on its local (batch, head) block with a
    static per-shard shape instead of leaving a Pallas black box to GSPMD.
    """
    from repro.kernels.decode_attention.ops import (decode_attention,
                                                    decode_plan,
                                                    paged_decode_attention)
    B, _, T = q.shape[:3]
    S = kv_pos.shape[-1]
    lengths, starts = _live_bounds(B, T, cache_start, kv_length, kv_start)
    if window > 0 and starts is not None:
        # contiguous layout (the kv_start contract): slot j holds position
        # j - start, so keys at or below start + q_pos - window are outside
        # the sliding window of the EARLIEST query (t=0) — tighten the start
        # bound to skip their blocks entirely (they were already
        # window-masked; this changes no output)
        qp = q_pos[:, 0].astype(jnp.int32)
        starts = jnp.maximum(starts, starts + qp - window + 1)
    impl, in_place = decode_plan(cfg.decode_impl, width=S,
                                 paged=table is not None,
                                 mesh=mesh is not None)
    if not in_place:
        k = _layer_view(k, layer, table, S)
        v = _layer_view(v, layer, table, S)
        layer = table = None
    if mesh is not None:
        # paged + mesh reuses the dense shard_map path on the gathered
        # logical view — the gather is a per-shard-local permutation once
        # pools stay unsharded on batch
        from repro.distributed.shard_wrap import sharded_decode_attention
        if starts is None:
            starts = jnp.zeros((B,), jnp.int32)
        return sharded_decode_attention(
            mesh, q, k.astype(q.dtype), v.astype(q.dtype), q_pos,
            kv_pos, lengths, starts, window=window, impl=impl)
    if table is not None:
        # the paged flash kernel consumes the layer's block pools directly
        return paged_decode_attention(
            q, k.astype(q.dtype), v.astype(q.dtype), table, q_pos, kv_pos,
            lengths, starts, layer, window=window, impl=impl)
    return decode_attention(q, k.astype(q.dtype), v.astype(q.dtype),
                            q_pos, kv_pos, lengths, starts, layer,
                            window=window, impl=impl)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    hd = cfg.resolved_head_dim
    if cfg.cache_layout == "paged":
        return init_paged_kv_cache(cfg, batch, max_len, dtype)
    if cfg.attention_kind == "mla":
        return {
            "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
            "pos": jnp.full((batch, max_len), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, cfg.num_kv_heads, max_len, hd), dtype),
        "v": jnp.zeros((batch, cfg.num_kv_heads, max_len, hd), dtype),
        "pos": jnp.full((batch, max_len), -1, jnp.int32),
    }


def init_paged_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                        *, num_blocks: Optional[int] = None,
                        table=None) -> dict:
    """Paged layer cache (DESIGN.md §13).

    The *logical* width stays exactly ``max_len`` (the ``pos`` array is
    byte-identical to the dense layout's, and every gather slices the
    block-rounded physical view back to it — which is what makes paged
    outputs bit-exact against dense, not merely close); only the physical
    pools are rounded up to whole blocks.

    Without ``table``, each row owns a contiguous identity stripe of the
    pool — the zero-bookkeeping layout the pure-functional paths
    (``generate``, one-pass resume, drafted decode) use, exercising the
    same paged read/write machinery as the allocator-managed serving
    engine.  ``num_blocks``/``table`` let the serving engine supply its own
    pool size (with the block-0 sink) and allocator-issued tables.
    """
    bs = cfg.kv_block_size
    nb = -(-max_len // bs)                   # physical blocks per row
    if table is None:
        table = (jnp.arange(batch * nb, dtype=jnp.int32).reshape(batch, nb))
        if num_blocks is None:
            num_blocks = batch * nb
    else:
        table = jnp.asarray(table, jnp.int32)
        assert table.shape == (batch, nb), (table.shape, (batch, nb))
        assert num_blocks is not None
    if cfg.attention_kind == "mla":
        return {
            "ckv": jnp.zeros((num_blocks, bs, cfg.kv_lora_rank), dtype),
            "krope": jnp.zeros((num_blocks, bs, cfg.qk_rope_head_dim), dtype),
            "pos": jnp.full((batch, max_len), -1, jnp.int32),
            "table": table,
        }
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((num_blocks, cfg.num_kv_heads, bs, hd), dtype),
        "v": jnp.zeros((num_blocks, cfg.num_kv_heads, bs, hd), dtype),
        "pos": jnp.full((batch, max_len), -1, jnp.int32),
        "table": table,
    }


def _cache_write(buf, update, start, layer, axis: int = -2):
    """Write ``update`` (length T) into layer ``layer`` of the stacked cache
    leaf ``buf`` (L, B, ...) at slot ``start`` on ``axis`` — in place, one
    dynamic_update_slice; nothing else of the stack is touched.

    start: scalar — one slot for the whole batch (prefill / lockstep decode)
    — or (B,) int32 — per-row slots, required by the serving slot scheduler
    whose slots sit at different decode depths (DESIGN.md §6).  The per-row
    form is a vmap'd dynamic_update_slice (a scatter), writing the same
    values at the same indices as the scalar form does row by row.
    """
    update = update.astype(buf.dtype)[None]
    axis = axis % buf.ndim

    def write(b, u, s):
        idx = [layer] + [0] * (b.ndim - 1)
        idx[axis] = s
        return jax.lax.dynamic_update_slice(b, u, idx)
    start = jnp.asarray(start, jnp.int32)
    if start.ndim == 0:
        return write(buf, update, start)
    axis -= 1                                    # inside the per-row vmap
    return jax.vmap(write, in_axes=(1, 1, 0), out_axes=1)(buf, update, start)


def _paged_write(pool, update, start, table, s_logical: int, layer):
    """Paged counterpart of ``_cache_write``: scatter a T-token update into
    layer ``layer`` of the stacked physical block pools through the row's
    block table.

    pool: (L, NB, Hkv, bs, D) or (L, NB, bs, D); update: (B, Hkv, T, D) /
    (B, T, D); start: scalar or (B,) int32; s_logical: the logical cache
    width (the ``pos`` array's, which may be short of ``nb * bs`` by the
    block-rounding slack).  Slot mapping matches the dense DUS semantics
    exactly — the effective start is clamped to ``s_logical - T`` so the
    whole window fits, and token t lands at logical slot ``start + t``
    (physical ``pool[table[b, (start+t) // bs], ..., (start+t) % bs]``).

    Two regimes: a large block-aligned update (prefill) scatters whole
    blocks; a short update (decode step / draft block, T <=
    DECODE_BLOCK_MAX_T) scatters per token.  Both are plain jnp scatters —
    the layout transform is memory-bound and XLA-friendly; only the
    attention *read* has a Pallas kernel.
    """
    update = update.astype(pool.dtype)
    bs = pool.shape[-2]
    B, nb = table.shape
    S = s_logical                     # clamp like dense DUS at this width
    gqa = pool.ndim == 5
    T = update.shape[2] if gqa else update.shape[1]
    start = jnp.asarray(start, jnp.int32)
    s0 = jnp.clip(jnp.broadcast_to(start.reshape(-1), (B,)), 0, S - T)
    if jnp.ndim(start) == 0 and T >= bs:
        # block-aligned prefill: the only scalar-start large-T callers write
        # at slot 0 (prefill / verify_and_prefill), so start % bs == 0
        # holds.  A ragged tail is zero-padded to a whole block — the extra
        # slots stay pos == -1 (masked) until a later decode write claims
        # them.
        pad = (-T) % bs
        if pad:
            width = [(0, 0)] * update.ndim
            width[2 if gqa else 1] = (0, pad)
            update = jnp.pad(update, width)
        nbw = (T + pad) // bs
        b0 = s0 // bs                                       # (B,)
        rows = jnp.arange(B)
        if gqa:
            chunks = update.reshape(B, update.shape[1], nbw, bs, -1)
            for i in range(nbw):
                blk = table[rows, b0 + i]
                pool = pool.at[layer, blk].set(chunks[:, :, i])
        else:
            chunks = update.reshape(B, nbw, bs, -1)
            for i in range(nbw):
                blk = table[rows, b0 + i]
                pool = pool.at[layer, blk].set(chunks[:, i])
        return pool
    rows = jnp.arange(B)
    for t in range(T):
        idx = s0 + t
        blk = table[rows, idx // bs]
        off = idx % bs
        if gqa:
            pool = pool.at[layer, blk, :, off].set(update[:, :, t])
        else:
            pool = pool.at[layer, blk, off].set(update[:, t])
    return pool


def _paged_gather(pool, table, s_logical: int):
    """Dense logical view of a paged pool, sliced to the logical width:
    (B, Hkv, s_logical, D) / (B, s_logical, D) — shape-identical (and
    value-identical) to the dense cache buffer, so every downstream fp op
    runs bit-exactly the dense program.  Read-side fallback for the
    non-kernel attention paths; DCE'd by XLA when the paged Pallas kernel
    consumes the pools directly."""
    B, nb = table.shape
    g = jnp.take(pool, table.reshape(-1), axis=0)
    if pool.ndim == 4:
        NB, Hkv, bs, D = pool.shape
        return (g.reshape(B, nb, Hkv, bs, D).transpose(0, 2, 1, 3, 4)
                .reshape(B, Hkv, nb * bs, D)[:, :, :s_logical])
    NB, bs, D = pool.shape
    return g.reshape(B, nb * bs, D)[:, :s_logical]


# ------------------------------------------------------------------ GQA layer


def make_gqa(key, cfg: ModelConfig, dtype):
    hd = cfg.resolved_head_dim
    ks = split_keys(key, 4)
    p = {
        "wq": make_dense(ks[0], cfg.d_model, cfg.num_heads * hd, cfg.qkv_bias, dtype),
        "wk": make_dense(ks[1], cfg.d_model, cfg.num_kv_heads * hd, cfg.qkv_bias, dtype),
        "wv": make_dense(ks[2], cfg.d_model, cfg.num_kv_heads * hd, cfg.qkv_bias, dtype),
        "wo": make_dense(ks[3], cfg.num_heads * hd, cfg.d_model, False, dtype,
                         scale=1.0 / (cfg.num_heads * hd) ** 0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = make_rmsnorm(hd, dtype)
        p["k_norm"] = make_rmsnorm(hd, dtype)
    return p


def apply_gqa(p, cfg: ModelConfig, x, positions, *, cache=None, cache_start=None,
              layer=None, causal=True, kv_x=None, kv_positions=None,
              kv_length=None, kv_start=None, mesh=None):
    """GQA attention.

    x: (B, T, d).  With ``cache`` given, writes K/V at ``cache_start`` and
    attends over the whole cache (decode / incremental prefill).  ``cache``
    is the layer run's stacked cache (leaves with a leading layer axis) and
    ``layer`` (scalar int32) this layer's index in it: K/V are written into
    that layer in place and the whole stack is returned.  With ``kv_x`` given, performs cross-attention (no causal
    mask, no rope on kv unless positions supplied).  ``kv_length`` (scalar
    or (B,) int32) bounds the live cache extent for decode-shaped calls
    (T == 1 with cache): those are dispatched to the flash-decode kernel /
    length-bounded blocked path instead of full-S attention.
    """
    B, T, _ = x.shape
    hd = cfg.resolved_head_dim
    q = apply_dense(p["wq"], x).reshape(B, T, cfg.num_heads, hd).transpose(0, 2, 1, 3)
    src = kv_x if kv_x is not None else x
    S = src.shape[1]
    k = apply_dense(p["wk"], src).reshape(B, S, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)
    v = apply_dense(p["wv"], src).reshape(B, S, cfg.num_kv_heads, hd).transpose(0, 2, 1, 3)

    if cfg.qk_norm:
        q = apply_rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = apply_rmsnorm(p["k_norm"], k, cfg.norm_eps)

    if kv_x is None:
        kv_pos = positions
        if cfg.pos_embed == "rope":
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, kv_pos, cfg.rope_theta)
    else:
        kv_pos = kv_positions
        # cross-attention: no rope (whisper style learned enc positions)

    decode = _decode_shaped(cache, kv_x, causal, T, kv_length)
    new_cache = table = None
    if cache is not None:
        S_log = cache["pos"].shape[-1]
        if "table" in cache:
            table = _layer(cache["table"], layer)
            k_st = _paged_write(cache["k"], k, cache_start, table, S_log, layer)
            v_st = _paged_write(cache["v"], v, cache_start, table, S_log, layer)
        else:
            k_st = _cache_write(cache["k"], k, cache_start, layer)
            v_st = _cache_write(cache["v"], v, cache_start, layer)
        pos_st = _cache_write(cache["pos"], kv_pos.astype(jnp.int32),
                              cache_start, layer, axis=-1)
        new_cache = dict(cache, k=k_st, v=v_st, pos=pos_st)
        kv_pos = _layer(pos_st, layer)
        if decode:
            k, v = k_st, v_st                # the decode op reads the stack
        elif table is None:
            # the layer's slice from before the write, written alike: read
            # from the written stack, the attention's operand layout would
            # spread to the stack and cost a relayout copy of all of it
            k = _cache_write(_layer(cache["k"], layer)[None], k,
                             cache_start, 0)[0]
            v = _cache_write(_layer(cache["v"], layer)[None], v,
                             cache_start, 0)[0]
        else:
            k = _layer_view(k_st, layer, table, S_log)
            v = _layer_view(v_st, layer, table, S_log)

    if decode:
        # short-query decode (single token, or a k+1 draft-verify block):
        # flash-decode kernel with split-K and per-row cache-length early
        # exit (or the length-bounded blocked fallback)
        out = _decode_attention(cfg, q, k, v, positions, kv_pos,
                                window=cfg.sliding_window,
                                cache_start=cache_start, kv_length=kv_length,
                                kv_start=kv_start, mesh=mesh, layer=layer,
                                table=table)
    else:
        out = dot_product_attention(q, k.astype(q.dtype), v.astype(q.dtype),
                                    positions, kv_pos,
                                    window=cfg.sliding_window, causal=causal,
                                    impl=cfg.attn_impl)
    out = out.transpose(0, 2, 1, 3).reshape(B, T, cfg.num_heads * hd)
    return apply_dense(p["wo"], out.astype(x.dtype)), new_cache


# ------------------------------------------------------------------ MLA layer


def make_mla(key, cfg: ModelConfig, dtype):
    """DeepSeek-V3 multi-head latent attention.

    q path:  d -> q_lora -> norm -> H*(nope+rope)
    kv path: d -> (kv_lora + shared k_rope); kv_lora -> norm -> H*(nope + v)
    Cache stores only the compressed latent + shared rope key.
    """
    ks = split_keys(key, 6)
    H = cfg.num_heads
    qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    p = {
        "wkv_a": make_dense(ks[2], cfg.d_model,
                            cfg.kv_lora_rank + cfg.qk_rope_head_dim, False, dtype),
        "kv_norm": make_rmsnorm(cfg.kv_lora_rank, dtype),
        "wkv_b": make_dense(ks[3], cfg.kv_lora_rank,
                            H * (cfg.qk_nope_head_dim + cfg.v_head_dim), False, dtype),
        "wo": make_dense(ks[4], H * cfg.v_head_dim, cfg.d_model, False, dtype),
    }
    if cfg.q_lora_rank:
        p["wq_a"] = make_dense(ks[0], cfg.d_model, cfg.q_lora_rank, False, dtype)
        p["q_norm"] = make_rmsnorm(cfg.q_lora_rank, dtype)
        p["wq_b"] = make_dense(ks[1], cfg.q_lora_rank, H * qd, False, dtype)
    else:
        p["wq"] = make_dense(ks[0], cfg.d_model, H * qd, False, dtype)
    return p


def _mla_decode(cfg: ModelConfig, wkv_b, q_nope, q_rope, ckv, krope,
                q_pos, kv_pos, *, layer, table, cache_start, kv_length,
                kv_start, mesh) -> jnp.ndarray:
    """MLA decode in the absorbed form (DESIGN.md §15): each head's
    no-position query goes through the K half of ``wkv_b``, scores are
    taken against the cached latent (r) and the shared rotated key (rd),
    the output over the latent, then through the V half of ``wkv_b``.
    Nothing of the cache is decompressed.

    ckv/krope are the run's stacked latent cache (or paged pools with
    ``table``).  ``decode_plan`` (kernels/decode_attention/ops.py) decides
    whether the ``decode_attention_mla`` kernel reads the layer in place;
    every other path takes the same arithmetic in jnp on the layer's view.
    Returns (B, T, H * v_head_dim) float32."""
    from repro.kernels.decode_attention.ops import (decode_attention_mla,
                                                    decode_plan)
    B, H, T, nd = q_nope.shape
    r, vd = cfg.kv_lora_rank, cfg.v_head_dim
    S = kv_pos.shape[-1]
    w = wkv_b["kernel"].astype(q_nope.dtype).reshape(r, H, nd + vd)
    q_lat = jnp.einsum("bhtn,rhn->bhtr", q_nope, w[..., :nd],
                       preferred_element_type=jnp.float32)
    lengths, starts = _live_bounds(B, T, cache_start, kv_length, kv_start)
    impl, in_place = decode_plan(cfg.decode_impl, width=S,
                                 paged=table is not None,
                                 mesh=mesh is not None, latent=True)
    if not in_place:
        ckv = _layer_view(ckv, layer, table, S)
        krope = _layer_view(krope, layer, table, S)
        layer = None
    o = decode_attention_mla(q_lat, q_rope, ckv, krope, q_pos, kv_pos,
                             lengths, starts, layer,
                             scale=float(nd + cfg.qk_rope_head_dim) ** -0.5,
                             impl=impl)
    out = jnp.einsum("bhtr,rhv->bthv", o.astype(q_nope.dtype), w[..., nd:],
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, H * vd)


def apply_mla(p, cfg: ModelConfig, x, positions, *, cache=None, cache_start=None,
              layer=None, causal=True, kv_length=None, kv_start=None,
              mesh=None):
    """DeepSeek-V3 MLA; ``cache``/``layer`` as in ``apply_gqa``.  Decode
    (a short cached query block) runs absorbed over the latent cache
    (``_mla_decode``); prefill and full forwards decompress the latent."""
    B, T, _ = x.shape
    H = cfg.num_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    if cfg.q_lora_rank:
        q = apply_dense(p["wq_b"], apply_rmsnorm(p["q_norm"],
                                                 apply_dense(p["wq_a"], x), cfg.norm_eps))
    else:
        q = apply_dense(p["wq"], x)
    q = q.reshape(B, T, H, nd + rd).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = apply_dense(p["wkv_a"], x)
    ckv, k_rope = kv_a[..., :cfg.kv_lora_rank], kv_a[..., cfg.kv_lora_rank:]
    ckv = apply_rmsnorm(p["kv_norm"], ckv, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, None, :, :], positions, cfg.rope_theta)  # (B,1,T,rd)

    kv_pos = positions
    new_cache = None
    if cache is not None:
        S_log = cache["pos"].shape[-1]
        if "table" in cache:
            table = _layer(cache["table"], layer)
            ckv_st = _paged_write(cache["ckv"], ckv, cache_start, table,
                                  S_log, layer)
            krope_st = _paged_write(cache["krope"], k_rope[:, 0],
                                    cache_start, table, S_log, layer)
        else:
            table = None
            ckv_st = _cache_write(cache["ckv"], ckv, cache_start, layer)
            krope_st = _cache_write(cache["krope"], k_rope[:, 0],
                                    cache_start, layer)
        pos_st = _cache_write(cache["pos"], positions.astype(jnp.int32),
                              cache_start, layer, axis=-1)
        new_cache = dict(cache, ckv=ckv_st, krope=krope_st, pos=pos_st)
        kv_pos = _layer(pos_st, layer)
        if _decode_shaped(cache, None, causal, T, kv_length):
            out = _mla_decode(cfg, p["wkv_b"], q_nope, q_rope, ckv_st,
                              krope_st, positions, kv_pos, layer=layer,
                              table=table, cache_start=cache_start,
                              kv_length=kv_length, kv_start=kv_start,
                              mesh=mesh)
            return apply_dense(p["wo"], out.astype(x.dtype)), new_cache
        # prefill decompresses the layer's whole logical view
        ckv = _layer_view(ckv_st, layer, table, S_log)
        k_rope = _layer_view(krope_st, layer, table, S_log)[:, None]

    # decompress latent -> per-head K_nope and V
    kv = apply_dense(p["wkv_b"], ckv.astype(x.dtype))
    S = kv.shape[1]
    kv = kv.reshape(B, S, H, nd + vd).transpose(0, 2, 1, 3)
    k_nope, v = kv[..., :nd], kv[..., nd:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope.astype(x.dtype),
                                                  (B, H, S, rd))], axis=-1)
    qfull = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = dot_product_attention(qfull, k, v, positions, kv_pos,
                                window=0, causal=causal,
                                impl=cfg.attn_impl)
    out = out.transpose(0, 2, 1, 3).reshape(B, T, H * vd)
    return apply_dense(p["wo"], out.astype(x.dtype)), new_cache


# ------------------------------------------------------------------ dispatch


def make_attention(key, cfg: ModelConfig, dtype):
    if cfg.attention_kind == "mla":
        return make_mla(key, cfg, dtype)
    return make_gqa(key, cfg, dtype)


def apply_attention(p, cfg: ModelConfig, x, positions, **kw):
    if cfg.attention_kind == "mla":
        kw.pop("kv_x", None), kw.pop("kv_positions", None)
        return apply_mla(p, cfg, x, positions, **kw)
    return apply_gqa(p, cfg, x, positions, **kw)
