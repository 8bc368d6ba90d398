"""Pallas-TPU kernel for SPEC-RL KV-cache compaction (cache_gather).

After the fused verify+prefill forward, each row's accepted context
[left-padded prompt | draft[:n]] already sits *contiguously* in the cache at
slots [P - p_len, P + n).  Left-aligning it to the decode layout is therefore
a per-row circular shift along the sequence axis — not an arbitrary gather —
so the whole compaction is one fused dynamic-roll per (row, head) with a
single HBM read and write per cache buffer, replacing the old host-visible
``left_align`` + second prefill round trip.

Grid: one program per flattened (run, batch, head) row.  The per-row shift
arrives via scalar prefetch (SMEM) so it is available before the block DMA.
The roll is the TPU's sublane rotate (``pltpu.roll``: out[j] =
x[(j - shift) mod S], the same in interpret mode); wrapped-in slots carry
stale K/V but their cache positions are rewritten to -1 by the caller, and
position-masked attention never reads them (see DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# A whole (S, D) cache row is one block.  v5e has 128 MiB of VMEM; the
# limit leaves room for the compiler's own scratch, and caps a bfloat16
# row of D = 128 at S = 32768 (qwen3's max_seq_len).
ROLL_VMEM_LIMIT = 100 * 2 ** 20
ROLL_VMEM_SLACK = 4 * 2 ** 20


def _roll_kernel(shift_ref, in_ref, out_ref, pad_ref, *, seq_len: int):
    S, Sp = seq_len, pad_ref.shape[0]
    s = jax.lax.rem(shift_ref[pl.program_id(0)], S)
    # the TPU rotate handles 32-bit data over a whole number of (8, 128)
    # tiles: roll bf16 caches as float32 (exact both ways), in a scratch
    # row of Sp = S rounded up to 8 sublanes
    pad_ref[pl.ds(0, S), :] = in_ref[0].astype(jnp.float32)
    xp = pad_ref[...]                                 # (Sp, D)
    y = pltpu.roll(xp, s, 0)        # y[j] = xp[j - s] for j >= s
    if Sp != S:
        # j < s wraps to xp[Sp + j - s], a pad row: take x[S + j - s] from
        # a second rotation by s + Sp - S instead
        j = jax.lax.broadcasted_iota(jnp.int32, xp.shape, 0)
        y = jnp.where(j < s, pltpu.roll(xp, s + (Sp - S), 0), y)
    pad_ref[...] = y
    out_ref[0] = pad_ref[pl.ds(0, S), :].astype(out_ref.dtype)


def _padded_rows(S: int) -> int:
    return -(-S // 8) * 8


def _roll_vmem_bytes(S: int, D: int, itemsize: int) -> int:
    """Scoped VMEM one roll program needs: the double-buffered (S, D) input
    and output blocks, the float32 scratch row and up to three float32
    values of its size (the row, two rotations)."""
    return 4 * S * D * itemsize + 4 * _padded_rows(S) * D * 4


def cache_roll_pallas(buf, shift, *, interpret: bool = False):
    """buf: (R, S, D); shift: (R,) int32 in [0, S].

    Returns out with out[r, j] = buf[r, (j - shift[r]) mod S].
    """
    R, S, D = buf.shape
    need = _roll_vmem_bytes(S, D, buf.dtype.itemsize)
    if need > ROLL_VMEM_LIMIT:
        raise ValueError(f"cache_roll: a ({S}, {D}) {buf.dtype} row needs "
                         f"{need} B of VMEM, over {ROLL_VMEM_LIMIT}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R,),
        in_specs=[pl.BlockSpec((1, S, D), lambda r, shift_ref: (r, 0, 0))],
        out_specs=pl.BlockSpec((1, S, D), lambda r, shift_ref: (r, 0, 0)),
        scratch_shapes=[pltpu.VMEM((_padded_rows(S), D), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_roll_kernel, seq_len=S),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(need + ROLL_VMEM_SLACK, 16 * 2 ** 20)),
        interpret=interpret,
    )(shift.astype(jnp.int32), buf)


def _gather_kernel(tab_ref, pool_ref, out_ref):
    del tab_ref  # consumed by the index map, not the body
    out_ref[0, 0] = pool_ref[0]


def paged_gather_pallas(pool, table, *, interpret: bool = False):
    """Paged-cache gather: materialise logical rows from a block pool.

    pool: (NB, X, D) physical blocks (X = bs, or Hkv*bs with heads folded
    into the sublane dim); table: (R, nb) int32 block ids.  Returns
    (R, nb, X, D) with out[r, i] = pool[table[r, i]].

    The table rides scalar prefetch so each program's block DMA is
    redirected at *index-map* time — the same machinery the paged decode
    kernel uses — and the kernel body is a pure VMEM copy (the compaction
    counterpart of cache_roll for the §13 layout).
    """
    NB, X, D = pool.shape
    R, nb = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R, nb),
        in_specs=[pl.BlockSpec((1, X, D),
                               lambda r, i, tab_ref: (tab_ref[r, i], 0, 0))],
        out_specs=pl.BlockSpec((1, 1, X, D),
                               lambda r, i, tab_ref: (r, i, 0, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, nb, X, D), pool.dtype),
        interpret=interpret,
    )(table.astype(jnp.int32), pool)
