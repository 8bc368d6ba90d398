"""Pallas-TPU kernel for the RWKV6 (Finch) time-mix recurrence.

Per (batch, head) with data-dependent per-channel decay ``w_t``::

    y_t = r_t @ S_{t-1} + (r_t * u * k_t).sum() * v_t
    S_t = w_t[:, None] * S_{t-1} + k_t[:, None] * v_t[None, :]

TPU adaptation of the CUDA wkv kernels: grid walks (batch*heads) x time
tiles sequentially; the (head_dim, head_dim) state is carried in a VMEM
scratch accumulator across time tiles, so HBM traffic is one read of
r/k/v/w and one write of y — the state never leaves VMEM until the final
tile writes it out for decode-cache handoff.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
                s_scr, *, block_t: int):
    t_i = pl.program_id(1)

    @pl.when(t_i == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                    # (1, hd)
    hd = u.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1))

    def col(row):
        # (1, hd) -> (hd, 1) by a masked lane reduction (exact: one nonzero
        # term per row), which the TPU lowering supports where a transpose
        # of a single row is not
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    def body(i, _):
        # one timestep as (1, hd) rows: the TPU matmul needs 2-D operands
        r = r_ref[0, pl.ds(i, 1), :].astype(jnp.float32)
        k = k_ref[0, pl.ds(i, 1), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i, 1), :].astype(jnp.float32)
        w = w_ref[0, pl.ds(i, 1), :].astype(jnp.float32)
        s = s_scr[...]                                  # (hd, hd)
        bonus = jnp.sum(r * u * k, axis=1, keepdims=True)
        y = jax.lax.dot(r, s, preferred_element_type=jnp.float32) + bonus * v
        y_ref[0, pl.ds(i, 1), :] = y.astype(y_ref.dtype)
        s_scr[...] = col(w) * s + col(k) * v
        return 0

    jax.lax.fori_loop(0, block_t, body, 0)

    @pl.when(t_i == pl.num_programs(1) - 1)
    def _finish():
        sout_ref[0] = s_scr[...].astype(sout_ref.dtype)


def wkv_pallas(r, k, v, w, u, s0, *, block_t: int = 256,
               interpret: bool = False):
    """r/k/v/w: (BH, T, hd) float32; u: (H, hd); s0: (BH, hd, hd) f32.

    Returns (y (BH, T, hd) f32, s_final (BH, hd, hd) f32).
    BH = batch * heads; row bh maps to head bh % H for the bonus vector.
    """
    BH, T, hd = r.shape
    H = u.shape[0]
    block_t = min(block_t, T)
    pad_t = (-T) % block_t
    if pad_t:
        # pads: w=1 (no decay), k=0 (no update) -> state unchanged
        r = jnp.pad(r, ((0, 0), (0, pad_t), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_t), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad_t), (0, 0)), constant_values=1.0)
    Tp = r.shape[1]

    grid = (BH, Tp // block_t)
    y, s_final = pl.pallas_call(
        functools.partial(_wkv_kernel, block_t=block_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, hd), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, block_t, hd), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, block_t, hd), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, block_t, hd), lambda b, t: (b, t, 0)),
            # (H, 1, hd): the block's last two dims equal the array's
            pl.BlockSpec((1, 1, hd), lambda b, t, H=H: (b % H, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, t: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, hd), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tp, hd), jnp.float32),
            jax.ShapeDtypeStruct((BH, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(H, 1, hd), s0)
    return y[:, :T, :], s_final
