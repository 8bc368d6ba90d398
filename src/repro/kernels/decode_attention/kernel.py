"""Pallas-TPU flash-decode: split-K short-query GQA/MQA attention with
per-row cache-length early exit (DESIGN.md §7, §9).

The prefill-shaped flash kernel is degenerate at decode time: a T=1 query
gives ``block_q = 1`` — a single-row MXU tile — and every token pays
attention over the full allocated cache width S even when most slots are
empty.  This kernel is specialised for the decode shape instead:

* **Head×query packing.**  The ``G = Hq / Hkv`` query heads that share one
  KV head, times the T block queries (T == 1 for classic decode, k + 1 for
  a draft-verify block), are packed into the MXU *sublane* dimension, so
  each KV head's tile is consumed by one ``(G·T, Dk) × (Dk, block_k)``
  matmul rather than G·T single-row tiles, and each KV tile is fetched
  exactly once per group.

* **Split-K over all KV heads.**  The grid is ``(B, S / block_k)``: one
  program reads all ``Hkv`` heads of one row over one slot tile (cache
  slots are *split* across programs).  For each head it emits an
  online-softmax partial (row max ``m``, row sum ``l``, unnormalised
  accumulator ``acc``) for its slot range; a cheap second-stage jnp combine
  (`_combine`) merges the partials with the standard logsumexp rescaling.
  A program's fixed cost (grid step, DMA issue) is what a decode call pays
  for, not its bytes, so the tile is as wide as the shapes allow
  (`decode_block_k`): the widest of 512, 256 and 128 slots that divides S
  and keeps one K + V block within ``KV_BLOCK_BYTES``.

* **Per-row early exit.**  Per-row live bounds arrive via scalar prefetch:
  ``lengths`` (write offset + block width — essential for the serving slot
  engine, whose rows sit at different decode depths) and ``starts`` (the
  first live slot — the §3 compacted layout right-aligns context at the
  verify width, so a short accepted prefix has a dead left-pad region in
  front of it).  A split is live when its slot range meets
  [starts[b], lengths[b]) and the row has a live query; a dead split skips
  the matmuls and writes the softmax-neutral partial (m=-inf, l=0, acc=0).
  Its K/V/k_pos DMAs read the row's nearest live tile (`_kv_tile`: the
  first for the dead left pad, the last for the empty tail), the tile the
  neighbouring program reads too, so the pipeline elides the copy.

* **Stacked cache, read in place.**  ``k``/``v`` may be a whole run's
  stacked cache ``(L, B, Hkv, S, D)`` with the layer as one more scalar
  prefetch: the K/V index maps address ``(layer, b, :, split)`` with the
  layer axis squeezed, so a decode step reads its layer's live tiles
  straight out of the stack instead of a copied layer slice (DESIGN.md §3).
  A width that is not a whole number of 128-slot tiles pads one layer's
  slice, never the stack (``reads_in_place``).

* **Query-block contract.**  Query positions arrive as two scalars per
  row — ``q_pos0[b]`` (position of query 0) and ``q_len[b]`` (number of
  valid queries) — so query t sits at position ``q_pos0 + t`` when
  ``t < q_len`` and is fully masked (exact-zero output) otherwise.  This
  matches the decode layouts that reach the kernel: a done row has
  ``q_len == 0``; a draft block proposes a valid prefix of its T columns.
  The ops wrapper derives both from the (B, T) position array; arbitrary
  non-contiguous query positions belong on the ref/blocked paths.

* **Latent attention.**  ``decode_attention_mla_pallas`` runs MLA decode in
  the absorbed form over the cached latent and shared rope key (one KV
  "head" of width r + rd that all H query heads share), with the same
  splits, masks and combine (DESIGN.md §15).
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# one K + V block of the dense kernel at most: double-buffered, and for a
# bf16 cache upcast to float32, it takes four times this (8 MiB), well
# inside v5e's default scoped VMEM (16 MiB)
KV_BLOCK_BYTES = 2 * 1024 * 1024
TILE_WIDTHS = (512, 256, 128)

# Dispatch counters, bumped at trace time: ``decode_attn_inplace`` /
# ``decode_attn_sliced`` count a model's decode-attention calls by whether
# they read the stacked cache in place (``ops.decode_plan``);
# ``decode_attn_tile_<width>`` counts the dense kernel's traces by the slot
# tile it takes.  A traced op that jit finds in its cache is not counted
# again: reset and trace afresh (or run under ``jax.disable_jit()``).
COUNTS = collections.Counter()


def _neutral(m_ref, l_ref, acc_ref):
    """A dead split's softmax-neutral partial, ignored by the combine."""
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _slot_mask(kpos, b, start, len_ref, start_ref, qpos0_ref, qlen_ref,
               shape, T: int, window: int):
    """(rows, block_k) mask of one split: sublane row r is query r % T (at
    position qpos0 + r % T, valid while below qlen); a slot counts where
    its position is live and causal, inside the window, and within the
    row's [starts, lengths)."""
    t_idx = jax.lax.broadcasted_iota(jnp.int32, shape, 0) % T
    qpos = qpos0_ref[b] + t_idx
    mask = (kpos >= 0) & (kpos <= qpos) & (t_idx < qlen_ref[b])
    if window > 0:
        mask &= (qpos - kpos) < window
    j = start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask &= (j < len_ref[b]) & (j >= start_ref[b])
    return mask


def _write_partial(s, mask, v, m_ref, l_ref, acc_ref):
    """One split's online-softmax partial of scores ``s`` over values v."""
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)                # (rows, 1)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = jnp.sum(p, axis=1, keepdims=True)
    acc_ref[0, 0, 0] = jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)        # (rows, Dv)


def _split_live(s, block_k: int, length, start, qlen):
    """Whether split s of a row is live: its slots meet the row's live
    range [start, length) and the row has a live query."""
    return (s * block_k < length) & ((s + 1) * block_k > start) & (qlen > 0)


def _kv_tile(s, block_k: int, nsplit: int, length, start, qlen):
    """The tile split s of a row reads: s itself while live; a dead split
    reads the row's nearest live tile instead (the first for the dead left
    pad, the last for the empty tail), which the neighbouring program of
    the row reads too, so the pipeline elides the copy.  A row with no
    live tile reads tile ``start // block_k`` in every split."""
    first = jnp.minimum(start // block_k, nsplit - 1)
    row_live = (start < length) & (qlen > 0)
    last = jnp.where(row_live, (length - 1) // block_k, first)
    return jnp.clip(s, first, last)


def _decode_body(b, s_i, len_ref, start_ref, qpos0_ref, qlen_ref, kpos_ref,
                 q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *, scale: float,
                 window: int, block_k: int, T: int):
    """One program: split ``s_i`` of row ``b`` for every KV head of the
    block (q: (1, H, G*T, Dk); k/v: (1, H, block_k, D); partials (1, H, 1,
    G*T, .)), each head's partial as `_write_partial` makes it, the H heads
    as the batch of one dot_general (faster on v5e than a loop over them).
    The slot mask depends on the row and split alone, so the heads share
    it."""
    start = s_i * block_k
    live = _split_live(s_i, block_k, len_ref[b], start_ref[b], qlen_ref[b])

    @pl.when(jnp.logical_not(live))
    def _dead():
        _neutral(m_ref, l_ref, acc_ref)

    @pl.when(live)
    def _live():
        GT = q_ref.shape[2]
        kpos = kpos_ref[0, 0].astype(jnp.int32)          # (1, bk)
        mask = _slot_mask(kpos, b, start, len_ref, start_ref, qpos0_ref,
                          qlen_ref, (GT, block_k), T, window)[None]
        q = q_ref[0].astype(jnp.float32)                 # (H, G*T, Dk)
        k = k_ref[0].astype(jnp.float32)                 # (H, bk, Dk)
        v = v_ref[0].astype(jnp.float32)                 # (H, bk, Dv)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)                  # (H, G*T, bk)
        m = jnp.max(s, axis=2, keepdims=True)
        p = jnp.where(mask, jnp.exp(s - m), 0.0)
        m_ref[0, :, 0] = m
        l_ref[0, :, 0] = jnp.sum(p, axis=2, keepdims=True)
        acc_ref[0, :, 0] = jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)          # (H, G*T, Dv)


def _decode_kernel(len_ref, start_ref, qpos0_ref, qlen_ref, layer_ref,
                   kpos_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                   **kw):
    del layer_ref                    # read by the K/V index maps only
    _decode_body(pl.program_id(0), pl.program_id(1), len_ref, start_ref,
                 qpos0_ref, qlen_ref, kpos_ref, q_ref, k_ref, v_ref, m_ref,
                 l_ref, acc_ref, **kw)


def decode_block_k(S: int, Hkv: int, Dk: int, Dv: int, itemsize: int) -> int:
    """Slots one dense decode program reads for all Hkv heads: the widest
    of ``TILE_WIDTHS`` that divides S and keeps one K + V block within
    ``KV_BLOCK_BYTES`` (128 where none does).  A width that is not a whole
    number of 128-slot tiles reads ``min(S, 128)``-slot tiles of a padded
    layer slice."""
    if S % 128:
        return min(S, 128)
    for bk in TILE_WIDTHS:
        if S % bk == 0 and bk * Hkv * (Dk + Dv) * itemsize <= KV_BLOCK_BYTES:
            return bk
    return 128


def reads_in_place(S: int, block_k: int = 128) -> bool:
    """Whether a stacked cache of width S is read in place: a width that is
    not a whole number of ``block_k`` tiles is padded, and only one layer's
    slice of it may be (padding the stack would copy every layer)."""
    return S % min(block_k, S) == 0


def _as_stack(k, v, layer):
    """(k, v, layer) with a leading layer axis: a single layer's cache is
    a stack of one."""
    if layer is None:
        return k[None], v[None], jnp.zeros((1,), jnp.int32)
    return k, v, jnp.asarray(layer, jnp.int32).reshape(1)


def _split_tiles(k_pos, nsplit: int, block: int):
    """(B, nsplit * block) positions -> (B, nsplit, 1, block).

    Each split's positions become one (1, block) tile whose last two dims
    equal the array's, the block shape the TPU lowering accepts for any
    block width (a (1, block) block over (B, S) is not (8, 128)-aligned)."""
    return k_pos.astype(jnp.int32).reshape(k_pos.shape[0], nsplit, 1, block)


def _combine(m, l, acc):
    """Second-stage split-K merge over axis 2 (the split axis).

    m, l: (B, Hkv, nsplit, G*T, 1); acc: (B, Hkv, nsplit, G*T, Dv).
    Standard logsumexp rescale; fully-masked rows (every split neutral)
    come out exactly zero."""
    m, l = m[..., 0], l[..., 0]
    m_glob = jnp.max(m, axis=2)                          # (B, Hkv, G*T)
    coef = jnp.exp(m - m_glob[:, :, None, :])
    l_tot = jnp.sum(coef * l, axis=2)                    # (B, Hkv, G*T)
    acc_tot = jnp.sum(coef[..., None] * acc, axis=2)     # (B, Hkv, G*T, Dv)
    return acc_tot / jnp.where(l_tot > 0, l_tot, 1.0)[..., None]


def _paged_kernel(len_ref, start_ref, qpos0_ref, qlen_ref, layer_ref,
                  table_ref, kpos_ref, q_ref, k_ref, v_ref, m_ref, l_ref,
                  acc_ref, **kw):
    # the dense body on one KV head a block (grid (B, Hkv, nb)) — the block
    # table only redirects the K/V DMAs (see the index maps in
    # paged_decode_attention_pallas)
    del layer_ref, table_ref
    _decode_body(pl.program_id(0), pl.program_id(2), len_ref, start_ref,
                 qpos0_ref, qlen_ref, kpos_ref, q_ref, k_ref, v_ref, m_ref,
                 l_ref, acc_ref, **kw)


def paged_decode_attention_pallas(q, k_pool, v_pool, table, q_pos0, q_len,
                                  k_pos, lengths, starts, layer=None, *,
                                  window: int = 0, interpret: bool = False):
    """Flash-decode over a paged KV cache (DESIGN.md §13).

    Same split-K schedule and kernel body as ``decode_attention_pallas``,
    but K/V live in a physical block pool — ``k_pool``: (NB, Hkv, bs, Dk),
    ``v_pool``: (NB, Hkv, bs, Dv) — and each row's logical cache is defined
    by ``table``: (B, nb) int32 block ids.  The split axis of the grid *is*
    the logical block axis (``block_k == bs``), so the per-split K/V index
    maps simply translate split ``s`` through the prefetched table:
    ``table[b, s]``.  Dead splits (outside [starts, lengths)) redirect to
    physical block 0 — the allocator's pinned sink — exactly as the dense
    kernel redirects to its own block 0.  ``k_pos`` stays dense (B, S =
    nb*bs), so masking is untouched: outputs are bit-identical to running
    the dense kernel on the gathered cache.  With ``layer`` the pools are a
    run's stacked pools ``(L, NB, Hkv, bs, D)``, read in place at that layer.
    """
    B, Hq, T, Dk = q.shape
    k_pool, v_pool, layer = _as_stack(k_pool, v_pool, layer)
    _, NB, Hkv, bs, _ = k_pool.shape
    Dv = v_pool.shape[-1]
    nb = table.shape[1]
    S = nb * bs
    assert k_pos.shape == (B, S), (k_pos.shape, (B, S))
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, T, Dk).reshape(B, Hkv, G * T, Dk)
    scale = 1.0 / (Dk ** 0.5)

    def _live_split(s, len_ref, start_ref, b):
        return (s * bs < len_ref[b]) & ((s + 1) * bs > start_ref[b])

    def _kv_block(b, h, s, len_ref, start_ref, qp_ref, ql_ref, layer_ref,
                  table_ref):
        # live split s of row b reads physical block table[b, s] of the
        # layer's pool; dead splits re-fetch the sink (block 0) instead of
        # streaming recycled blocks (same-block DMA is elided)
        live = _live_split(s, len_ref, start_ref, b)
        return (layer_ref[0], jnp.where(live, table_ref[b, s], 0), h, 0, 0)

    def _kpos_block(b, h, s, len_ref, start_ref, *_):
        return (b, jnp.where(_live_split(s, len_ref, start_ref, b), s, 0),
                0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(B, Hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, 1, bs), _kpos_block),
            pl.BlockSpec((1, 1, G * T, Dk), lambda b, h, s, *_: (b, h, 0, 0)),
            pl.BlockSpec((None, 1, 1, bs, Dk), _kv_block),   # layer squeezed
            pl.BlockSpec((None, 1, 1, bs, Dv), _kv_block),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, G * T, 1),
                         lambda b, h, s, *_: (b, h, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, G * T, 1),
                         lambda b, h, s, *_: (b, h, s, 0, 0)),
            pl.BlockSpec((1, 1, 1, G * T, Dv),
                         lambda b, h, s, *_: (b, h, s, 0, 0)),
        ],
    )
    m, l, acc = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, window=window,
                          block_k=bs, T=T),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, nb, G * T, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, nb, G * T, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, nb, G * T, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), starts.astype(jnp.int32),
      q_pos0.astype(jnp.int32), q_len.astype(jnp.int32), layer,
      table.astype(jnp.int32), _split_tiles(k_pos, nb, bs), qg, k_pool,
      v_pool)
    out = _combine(m, l, acc)                            # (B, Hkv, G*T, Dv)
    return out.reshape(B, Hkv, G, T, Dv).reshape(B, Hq, T, Dv)


def decode_attention_pallas(q, k, v, q_pos0, q_len, k_pos, lengths, starts,
                            layer=None, *, window: int = 0,
                            interpret: bool = False):
    """q: (B, Hq, T, Dk); k: (B, Hkv, S, Dk); v: (B, Hkv, S, Dv);
    q_pos0/q_len: (B,) int32 query-block descriptors (query t lives at
    position q_pos0 + t iff t < q_len); k_pos: (B, S) int32;
    lengths/starts: (B,) int32 live bounds (slot j live iff
    starts[b] <= j < lengths[b]).  With ``layer`` (a scalar int32), k/v
    are a run's stacked cache (L, B, Hkv, S, D), read in place at that
    layer; k_pos is that layer's positions.

    The grid is (B, S / block_k), block_k from ``decode_block_k``: one
    program per (row, split) over all Hkv heads.  Returns (B, Hq, T, Dv)
    float32.  Dk and Dv may differ (MLA)."""
    B, Hq, T, Dk = q.shape
    k, v, layer = _as_stack(k, v, layer)
    Hkv, S = k.shape[2], k.shape[3]
    Dv = v.shape[-1]
    G = Hq // Hkv
    block_k = decode_block_k(S, Hkv, Dk, Dv, k.dtype.itemsize)
    COUNTS["decode_attn_tile_%d" % block_k] += 1
    pad_s = (-S) % block_k
    if pad_s:
        # a partial last tile: pad the layer's slice, never the stack
        k = jax.lax.dynamic_slice_in_dim(k, layer[0], 1, 0)
        v = jax.lax.dynamic_slice_in_dim(v, layer[0], 1, 0)
        layer = jnp.zeros((1,), jnp.int32)
        width = ((0, 0), (0, 0), (0, 0), (0, pad_s), (0, 0))
        k, v = jnp.pad(k, width), jnp.pad(v, width)
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad_s)), constant_values=-1)
    Sp = k.shape[3]
    nsplit = Sp // block_k
    # pack (G, T) into the sublane dim: row g*T + t
    qg = q.reshape(B, Hkv, G, T, Dk).reshape(B, Hkv, G * T, Dk)
    scale = 1.0 / (Dk ** 0.5)

    def _tile(b, s, len_ref, start_ref, qlen_ref):
        return _kv_tile(s, block_k, nsplit, len_ref[b], start_ref[b],
                        qlen_ref[b])

    def _kv_block(b, s, len_ref, start_ref, qp_ref, ql_ref, layer_ref):
        return (layer_ref[0], b, 0, _tile(b, s, len_ref, start_ref, ql_ref),
                0)

    def _kpos_block(b, s, len_ref, start_ref, qp_ref, ql_ref, *_):
        return (b, _tile(b, s, len_ref, start_ref, ql_ref), 0, 0)

    def _out(b, s, *_):
        return (b, 0, s, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, nsplit),
        in_specs=[
            pl.BlockSpec((1, 1, 1, block_k), _kpos_block),
            pl.BlockSpec((1, Hkv, G * T, Dk), lambda b, s, *_: (b, 0, 0, 0)),
            pl.BlockSpec((None, 1, Hkv, block_k, Dk), _kv_block),  # layer squeezed
            pl.BlockSpec((None, 1, Hkv, block_k, Dv), _kv_block),
        ],
        out_specs=[
            pl.BlockSpec((1, Hkv, 1, G * T, 1), _out),
            pl.BlockSpec((1, Hkv, 1, G * T, 1), _out),
            pl.BlockSpec((1, Hkv, 1, G * T, Dv), _out),
        ],
    )
    m, l, acc = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, window=window,
                          block_k=block_k, T=T),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, nsplit, G * T, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, nsplit, G * T, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, nsplit, G * T, Dv), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(lengths.astype(jnp.int32), starts.astype(jnp.int32),
      q_pos0.astype(jnp.int32), q_len.astype(jnp.int32), layer,
      _split_tiles(k_pos, nsplit, block_k), qg, k, v)
    out = _combine(m, l, acc)                            # (B, Hkv, G*T, Dv)
    return out.reshape(B, Hkv, G, T, Dv).reshape(B, Hq, T, Dv)


# latent tile of the MLA kernel: whole tiles of it read the stack in place
MLA_BLOCK_K = 256


def _mla_kernel(len_ref, start_ref, qpos0_ref, qlen_ref, layer_ref,
                kpos_ref, ql_ref, qr_ref, c_ref, r_ref, m_ref, l_ref,
                acc_ref, *, scale: float, block_k: int, T: int):
    del layer_ref                    # read by the latent index maps only
    b = pl.program_id(0)
    s_i = pl.program_id(1)
    start = s_i * block_k
    live = ((start < len_ref[b]) & (start + block_k > start_ref[b])
            & (qlen_ref[b] > 0))

    @pl.when(jnp.logical_not(live))
    def _dead():
        _neutral(m_ref, l_ref, acc_ref)

    @pl.when(live)
    def _live():
        ql = ql_ref[0].astype(jnp.float32)               # (H*T, r)
        qr = qr_ref[0].astype(jnp.float32)               # (H*T, rd)
        c = c_ref[0].astype(jnp.float32)                 # (bk, r)
        kr = r_ref[0].astype(jnp.float32)                # (bk, rd)
        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(ql, c, dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, kr, dims,
                                   preferred_element_type=jnp.float32)
             ) * scale
        kpos = kpos_ref[0, 0].astype(jnp.int32)          # (1, bk)
        mask = _slot_mask(kpos, b, start, len_ref, start_ref, qpos0_ref,
                          qlen_ref, s.shape, T, 0)
        _write_partial(s, mask, c, m_ref, l_ref, acc_ref)


def decode_attention_mla_pallas(q_lat, q_rope, ckv, krope, q_pos0, q_len,
                                k_pos, lengths, starts, layer=None, *,
                                scale: float, block_k: int = MLA_BLOCK_K,
                                interpret: bool = False):
    """Absorbed latent-attention decode (DeepSeek-V3 MLA, DESIGN.md §15).

    q_lat: (B, H, T, r) — each head's no-position query already multiplied
    into the K half of ``wkv_b``; q_rope: (B, H, T, rd) rotated; ckv:
    (B, S, r) the cached (normed) latent and krope: (B, S, rd) the shared
    rotated key, or with ``layer`` a run's stacks (L, B, S, r) / (L, B, S,
    rd) read in place at that layer.  Score of slot j for head h is
    (q_lat[h] . ckv[j] + q_rope[h] . krope[j]) * scale; the output is the
    softmax-weighted latent (B, H, T, r) float32, to go through the V half
    of ``wkv_b``.

    The grid is (B, S / block_k): one program per (row, split) reads its
    latent and rope tiles once for all H heads (packed with the T queries
    into the sublane rows, row h*T + t).  Splits, per-row live bounds
    [starts, lengths), the query-block contract and the combine are the
    GQA kernel's; a row with no live query (finished) is dead in every
    split and reads no tile.  S must be a whole number of tiles."""
    B, H, T, r = q_lat.shape
    rd = q_rope.shape[-1]
    ckv, krope, layer = _as_stack(ckv, krope, layer)
    S = ckv.shape[2]
    block_k = min(block_k, S)
    assert S % block_k == 0, (S, block_k)
    nsplit = S // block_k
    HT = H * T
    ql = q_lat.reshape(B, HT, r)
    qr = q_rope.reshape(B, HT, rd)

    def _live_split(s, b, len_ref, start_ref, qlen_ref):
        # a finished row (no live query) reads nothing
        return ((s * block_k < len_ref[b]) & ((s + 1) * block_k > start_ref[b])
                & (qlen_ref[b] > 0))

    def _lat_block(b, s, len_ref, start_ref, qp_ref, ql_ref, layer_ref):
        # dead splits re-fetch tile 0 (same-block DMA is elided)
        live = _live_split(s, b, len_ref, start_ref, ql_ref)
        return (layer_ref[0], b, jnp.where(live, s, 0), 0)

    def _kpos_block(b, s, len_ref, start_ref, qp_ref, ql_ref, *_):
        live = _live_split(s, b, len_ref, start_ref, ql_ref)
        return (b, jnp.where(live, s, 0), 0, 0)

    def _out(b, s, *_):
        return (b, 0, s, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, nsplit),
        in_specs=[
            pl.BlockSpec((1, 1, 1, block_k), _kpos_block),
            pl.BlockSpec((1, HT, r), lambda b, s, *_: (b, 0, 0)),
            pl.BlockSpec((1, HT, rd), lambda b, s, *_: (b, 0, 0)),
            pl.BlockSpec((None, 1, block_k, r), _lat_block),  # layer squeezed
            pl.BlockSpec((None, 1, block_k, rd), _lat_block),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, HT, 1), _out),
            pl.BlockSpec((1, 1, 1, HT, 1), _out),
            pl.BlockSpec((1, 1, 1, HT, r), _out),
        ],
    )
    m, l, acc = pl.pallas_call(
        functools.partial(_mla_kernel, scale=scale, block_k=block_k, T=T),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, nsplit, HT, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, nsplit, HT, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, nsplit, HT, r), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention_mla",
    )(lengths.astype(jnp.int32), starts.astype(jnp.int32),
      q_pos0.astype(jnp.int32), q_len.astype(jnp.int32), layer,
      _split_tiles(k_pos, nsplit, block_k), ql, qr, ckv, krope)
    return _combine(m, l, acc).reshape(B, H, T, r)
