"""Pallas-TPU flash attention (causal, GQA, optional sliding window,
position-based padding masks).

This is the hot spot of SPEC-RL's *verification* pass (a prefill-shaped
teacher-forced forward over the draft) and of prefill generally.

Tiling: grid = (batch, q_heads, q_tiles, kv_tiles), kv innermost.  Online
softmax state (row max `m`, row sum `l`, output accumulator) lives in VMEM
scratch sized (block_q, head_dim) — chosen so q/k/v tiles plus accumulators
fit comfortably in 16 MB VMEM with MXU-aligned (multiple-of-128) tiles at
production sizes.  GQA is expressed in the k/v BlockSpec index maps
(`h // group`), so kv tiles are fetched once per q-head group member without
materialising repeated heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, window: int,
                  causal: bool):
    kv_i = pl.program_id(3)

    @pl.when(kv_i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                 # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                 # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)                 # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qpos_ref[0, 0]                               # (bq, 1)
    kpos = kpos_ref[0, 0]                               # (1, bk)
    mask = kpos >= 0
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                 # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = corr * acc_scr[...] + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(kv_i == pl.num_programs(3) - 1)
    def _finish():
        l = l_scr[...]
        o = acc_scr[...] / jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = o.astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, q_pos, k_pos, *, causal: bool = True,
                           window: int = 0, block_q: int = 128,
                           block_k: int = 128, interpret: bool = False):
    """q: (B, Hq, T, D); k/v: (B, Hkv, S, D); q_pos: (B, T); k_pos: (B, S).

    Returns (B, Hq, T, D) float32 attention output.
    """
    B, Hq, T, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    group = Hq // Hkv
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    pad_t = (-T) % block_q
    pad_s = (-S) % block_k
    if pad_t:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_t)), constant_values=-1)
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad_s)), constant_values=-1)
    Tp, Sp = q.shape[2], k.shape[2]

    nq, nk = Tp // block_q, Sp // block_k
    grid = (B, Hq, nq, nk)
    scale = 1.0 / (D ** 0.5)
    # one position tile per q / kv block, shaped so the block's last two
    # dims equal the array's: (bq, 1) column / (1, bk) row tiles are
    # accepted by the TPU lowering at any block size
    q_pos = q_pos.astype(jnp.int32).reshape(B, nq, block_q, 1)
    k_pos = k_pos.astype(jnp.int32).reshape(B, nk, 1, block_k)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, window=window,
                          causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, t, s: (b, t, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_k), lambda b, h, t, s: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, t, s: (b, h, t, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, t, s, g=group: (b, h // g, s, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, t, s, g=group: (b, h // g, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, t, s: (b, h, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Tp, D), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q_pos, k_pos, q, k, v)
    return out[:, :, :T, :]
