"""Pure-jnp oracles for short-query decode attention.

Two reference implementations with identical semantics:

``decode_attention_ref``     the naive oracle — materialises the full
    (B, Hkv, G, T, S) score tensor.  At T == 1 it is term-for-term the
    decode slice of ``repro.models.attention.dot_product_attention`` (same
    einsum, same masking, same fully-masked-row zeroing), so routing decode
    through it is bit-identical to the legacy naive decode path.

``decode_attention_blocked`` the length-bounded flash path — a
    ``lax.while_loop`` over KV chunks that stops at the last *live* chunk
    (ceil(max(lengths) / block_k)), so per-token decode work is
    proportional to the deepest live cache row instead of the allocated
    cache width S.  This is the CPU/dry-run default for decode-shaped
    calls (see models/attention.py and DESIGN.md §7); the Pallas kernel
    additionally early-exits per *row*.

The query axis T is 1 for classic decode and ``k + 1`` for a draft-verify
block (DESIGN.md §9): the current token plus k drafted continuation tokens
forwarded together, each attending causally over the per-row live cache
bounds (the block's own K/V are already written into the cache, so
within-block causality is ordinary position masking).

Masking contract (shared with the kernel): key slot j of row b contributes
to query t iff ``k_pos[b, j] >= 0`` and ``k_pos[b, j] <= q_pos[b, t]`` and
(window) ``q_pos[b, t] - k_pos[b, j] < window`` and
``starts[b] <= j < lengths[b]``.  ``starts``/``lengths`` are performance
bounds — callers derive them from the cache layout (first live slot /
write offset + block width), so every slot outside [starts, lengths)
already carries ``pos == -1`` — but both are also enforced as masks so
ref/blocked/pallas agree even on adversarial inputs.  A query with
``q_pos == -1`` (done row / draft padding) comes out exactly zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _norm_inputs(q, q_pos, lengths, starts, S):
    """q: (B, Hq, T, D) unchanged; q_pos: (B,)/(B, 1) at T == 1, else
    strictly (B, T) — a (B,) position for a T > 1 block is ambiguous (the
    Pallas kernel's consecutive-position contract vs same-position
    broadcast), so every impl rejects it rather than diverging."""
    assert q.ndim == 4, f"decode attention wants (B, Hq, T, D); got {q.shape}"
    B, _, T = q.shape[:3]
    q_pos = q_pos.reshape(B, -1)
    if q_pos.shape != (B, T):
        raise ValueError(f"q_pos {q_pos.shape} must be (B, T)={B, T} for "
                         f"T > 1 query blocks")
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    lengths = jnp.minimum(lengths.reshape(B).astype(jnp.int32), S)
    if starts is None:
        starts = jnp.zeros((B,), jnp.int32)
    starts = jnp.clip(starts.reshape(B).astype(jnp.int32), 0, S)
    return q_pos, lengths, starts


def decode_attention_ref(q, k, v, q_pos, k_pos, lengths=None, starts=None, *,
                         window: int = 0):
    """q: (B, Hq, T, Dk); k: (B, Hkv, S, Dk); v: (B, Hkv, S, Dv);
    q_pos: (B,), (B, 1) or (B, T); k_pos: (B, S); lengths/starts: optional
    (B,) int32 live bounds (slot j live iff starts[b] <= j < lengths[b]).

    Returns (B, Hq, T, Dv) float32."""
    B, Hq, T = q.shape[:3]
    Hkv, S, Dk = k.shape[1], k.shape[2], k.shape[3]
    q_pos, lengths, starts = _norm_inputs(q, q_pos, lengths, starts, S)
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, T, Dk)
    scale = 1.0 / jnp.sqrt(jnp.asarray(Dk, jnp.float32))
    scores = jnp.einsum("bhgtd,bhsd->bhgts", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    kp = k_pos[:, None, None, None, :]
    qp = q_pos[:, None, None, :, None]
    mask = (kp >= 0) & (kp <= qp)
    if window > 0:
        mask &= (qp - kp) < window
    j = jnp.arange(S, dtype=jnp.int32)[None, None, None, None, :]
    mask &= j < lengths[:, None, None, None, None]
    mask &= j >= starts[:, None, None, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    any_valid = jnp.any(mask, axis=-1, keepdims=True)
    w = jnp.where(any_valid, w, 0.0)
    out = jnp.einsum("bhgts,bhsd->bhgtd", w, v.astype(jnp.float32))
    return out.reshape(B, Hq, T, v.shape[-1])


def decode_attention_blocked(q, k, v, q_pos, k_pos, lengths=None, starts=None,
                             *, window: int = 0, block_k: int = 128):
    """Length-bounded online-softmax decode (same signature/result as ref).

    A ``while_loop`` over KV chunks runs from chunk min(starts) // block_k
    to ceil(max(lengths) / block_k) — real work savings even under jit,
    since both trip bounds are dynamic.  Peak score memory is
    (B, Hkv, G, T, block_k)."""
    B, Hq, T = q.shape[:3]
    Hkv, S, Dk = k.shape[1], k.shape[2], k.shape[3]
    Dv = v.shape[-1]
    q_pos, lengths, starts = _norm_inputs(q, q_pos, lengths, starts, S)
    G = Hq // Hkv
    block_k = min(block_k, S)
    pad = (-S) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    qg = q.reshape(B, Hkv, G, T, Dk).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(Dk, jnp.float32))
    c0 = jnp.min(starts) // block_k
    n_live = (jnp.max(lengths) + block_k - 1) // block_k
    jidx = jnp.arange(block_k, dtype=jnp.int32)

    def cond(carry):
        return carry[0] < n_live

    def body(carry):
        c, m, l, acc = carry
        start = c * block_k
        k_b = jax.lax.dynamic_slice_in_dim(k, start, block_k, axis=2)
        v_b = jax.lax.dynamic_slice_in_dim(v, start, block_k, axis=2)
        p_b = jax.lax.dynamic_slice_in_dim(k_pos, start, block_k, axis=1)
        s = jnp.einsum("bhgtd,bhsd->bhgts", qg,
                       k_b.astype(jnp.float32)) * scale
        kp = p_b[:, None, :]                                  # (B, 1, bk)
        qp = q_pos[:, :, None]                                # (B, T, 1)
        mask = (kp >= 0) & (kp <= qp)                         # (B, T, bk)
        if window > 0:
            mask &= (qp - kp) < window
        j = (start + jidx)[None, None, :]
        mask &= (j < lengths[:, None, None]) & (j >= starts[:, None, None])
        maskb = mask[:, None, None, :, :]                     # (B,1,1,T,bk)
        s = jnp.where(maskb, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(maskb, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = corr * acc + jnp.einsum("bhgts,bhsd->bhgtd", p,
                                      v_b.astype(jnp.float32))
        return c + 1, m_new, l, acc

    init = (c0.astype(jnp.int32),
            jnp.full((B, Hkv, G, T, 1), NEG_INF, jnp.float32),
            jnp.zeros((B, Hkv, G, T, 1), jnp.float32),
            jnp.zeros((B, Hkv, G, T, Dv), jnp.float32))
    _, m, l, acc = jax.lax.while_loop(cond, body, init)
    out = acc / jnp.where(l > 0, l, 1.0)
    return out.reshape(B, Hq, T, Dv)


def mla_decode_attention_ref(q_lat, q_rope, ckv, krope, q_pos, k_pos,
                             lengths=None, starts=None, *, scale: float):
    """Absorbed latent-attention decode in jnp (the kernel's oracle, and the
    path for T > 1 blocks, the CPU, widths that are not whole tiles, paged
    and mesh caches).

    q_lat: (B, H, T, r); q_rope: (B, H, T, rd); ckv: (B, S, r); krope:
    (B, S, rd); q_pos/k_pos/lengths/starts as in ``decode_attention_ref``.
    Returns the softmax-weighted latent (B, H, T, r) float32."""
    S = ckv.shape[1]
    q_pos, lengths, starts = _norm_inputs(q_lat, q_pos, lengths, starts, S)
    scores = (jnp.einsum("bhtr,bsr->bhts", q_lat.astype(jnp.float32),
                         ckv.astype(jnp.float32))
              + jnp.einsum("bhtd,bsd->bhts", q_rope.astype(jnp.float32),
                           krope.astype(jnp.float32))) * scale
    kp = k_pos[:, None, None, :]
    qp = q_pos[:, None, :, None]
    j = jnp.arange(S, dtype=jnp.int32)[None, None, None, :]
    mask = ((kp >= 0) & (kp <= qp) & (j < lengths[:, None, None, None])
            & (j >= starts[:, None, None, None]))
    w = jax.nn.softmax(jnp.where(mask, scores, NEG_INF), axis=-1)
    w = jnp.where(jnp.any(mask, axis=-1, keepdims=True), w, 0.0)
    return jnp.einsum("bhts,bsr->bhtr", w, ckv.astype(jnp.float32))
