"""shard_map boundaries around the Pallas kernels (DESIGN.md §8).

GSPMD partitions plain jnp code automatically, but a ``pl.pallas_call`` is a
black box to the partitioner: under a mesh it must be wrapped in
``shard_map`` so each device runs the kernel on its *local* block with a
static per-shard shape (grids, BlockSpecs and scalar-prefetch lengths are
shape-derived).  This module is the single place those wrappers live:

* ``sharded_decode_attention`` — batch over ``data``, query/KV heads over
  ``model`` (head sharding only when both head counts divide; uneven-head
  GQA/MQA replicates heads, mirroring ``param_spec``'s kv rule);
* ``sharded_spec_verify``     — batch over ``data``;
* ``shard_map_call``          — generic helper for the cache-surgery kernels
  (``cache_gather`` rolls shard batch rows, ``cache_slot_write`` shards the
  KV head axis with slot indices replicated — see models/model.py).

Every wrapper degrades: when the mesh lacks the relevant axis or a dimension
does not divide, it falls back to the unwrapped (GSPMD- or single-device-)
call, so callers thread ``mesh`` unconditionally.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def batch_axis_name(mesh: Mesh):
    """The data axes as a PartitionSpec entry (None when absent)."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names
                 and mesh.shape[a] > 1)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def _axis_size(mesh: Mesh, ax) -> int:
    if ax is None:
        return 1
    axes = ax if isinstance(ax, tuple) else (ax,)
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def batch_shardable(mesh: Optional[Mesh], batch: int) -> bool:
    if mesh is None:
        return False
    ax = batch_axis_name(mesh)
    d = _axis_size(mesh, ax)
    return ax is not None and d > 1 and batch % d == 0 and batch >= d


def model_axis(mesh: Mesh, *dims: int):
    """'model' when present and every ``dim`` divides it, else None."""
    if "model" not in mesh.axis_names or mesh.shape["model"] <= 1:
        return None
    m = mesh.shape["model"]
    if all(d % m == 0 and d >= m for d in dims):
        return "model"
    return None


def shard_map_call(mesh: Mesh, fn, in_specs, out_specs, *args):
    """One-shot shard_map application (per-shard shapes stay static)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


# ------------------------------------------------------------ decode attention


def sharded_decode_attention(mesh: Optional[Mesh], q, k, v, q_pos, k_pos,
                             lengths, starts, *, window: int = 0,
                             impl: str = "auto", block_k: int = 128):
    """Mesh-partitioned flash-decode attention.

    q: (B, Hq, T, Dk) (T == 1 classic decode, k+1 draft-verify block);
    k: (B, Hkv, S, Dk); v: (B, Hkv, S, Dv); q_pos: (B,) or (B, T);
    k_pos: (B, S); lengths/starts: (B,) int32 (must be materialised — no
    None — so the shard_map arg tree is static).  Batch shards over the
    data axes, heads over ``model`` when both Hq and Hkv divide it.
    """
    from repro.kernels.decode_attention.ops import decode_attention
    B, Hq = q.shape[0], q.shape[1]
    Hkv = k.shape[1]

    d_ax = batch_axis_name(mesh) if batch_shardable(mesh, B) else None
    h_ax = model_axis(mesh, Hq, Hkv) if mesh is not None else None
    if d_ax is None and h_ax is None:
        return decode_attention(q, k, v, q_pos, k_pos, lengths, starts,
                                window=window, impl=impl, block_k=block_k)

    def inner(q, k, v, qp, kp, ln, st):
        return decode_attention(q, k, v, qp, kp, ln, st, window=window,
                                impl=impl, block_k=block_k)

    head4 = P(d_ax, h_ax, None, None)
    rows = P(d_ax)
    qp_spec = rows if q_pos.ndim == 1 else P(d_ax, None)
    return shard_map_call(
        mesh, inner,
        (head4, head4, head4, qp_spec, P(d_ax, None), rows, rows),
        head4, q, k, v, q_pos, k_pos, lengths, starts)


# ------------------------------------------------------------------ spec verify


def sharded_spec_verify(mesh: Optional[Mesh], lp_curr, lp_prev, u, valid_len,
                        log_lenience):
    """Mesh-partitioned accept/first-reject reduction (batch over data)."""
    from repro.kernels.spec_verify.ops import spec_verify
    B = lp_curr.shape[0]
    if not batch_shardable(mesh, B):
        return spec_verify(lp_curr, lp_prev, u, valid_len, log_lenience)
    d_ax = batch_axis_name(mesh)
    r2, r1 = P(d_ax, None), P(d_ax)

    def inner(lc, lp, uu, vl, ll):
        return spec_verify(lc, lp, uu, vl, ll)

    return shard_map_call(
        mesh, inner, (r2, r2, r2, r1, P()), r1,
        lp_curr, lp_prev, u, valid_len,
        jnp.asarray(log_lenience, jnp.float32))
