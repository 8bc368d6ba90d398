"""Paged decode attention + paged gather/write kernels (DESIGN.md §13).

The paged flash kernel (scalar-prefetched block table redirecting K/V tile
DMAs) is checked in interpret mode against the gathered-dense oracle —
``gather_paged_kv`` + the already-tested ``decode_attention`` — across GQA
and MLA-shaped pools, shuffled and shared tables, dead rows, and sink
redirects.  ``paged_gather`` / ``paged_slot_write`` round-trips cover the
re-paging primitives the serving engine admits through.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cache_gather.ops import paged_gather
from repro.kernels.cache_slot_write.ops import paged_slot_write
from repro.kernels.decode_attention.ops import (decode_attention,
                                                gather_paged_kv,
                                                paged_decode_attention)


def _paged_case(B, Hq, Hkv, S, D, bs, seed=0, share=False):
    """Pool + shuffled table + mixed-depth positions for one decode step.

    Logical row b holds a left-padded context (pad, then [0, live-pad));
    its blocks are scattered through the pool in shuffled order.  With
    ``share`` the LAST row reuses row 0's table — aliased reads, the CoW
    read pattern."""
    rng = np.random.RandomState(seed)
    nb = -(-S // bs)
    NB = 1 + B * nb                       # block 0 = sink
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, Hq, 1, D))
    k_pool = jax.random.normal(ks[1], (NB, Hkv, bs, D))
    v_pool = jax.random.normal(ks[2], (NB, Hkv, bs, D))
    perm = rng.permutation(NB - 1) + 1    # never the sink
    table = perm[:B * nb].reshape(B, nb).astype(np.int32)
    if share:
        table[B - 1] = table[2]
    lengths = np.zeros(B, np.int32)
    starts = np.zeros(B, np.int32)
    q_pos = np.zeros(B, np.int32)
    kpos = np.full((B, S), -1, np.int32)
    for b in range(B):
        live = 0 if b == 0 else (S if b == 1 else int(rng.randint(1, S)))
        pad = int(rng.randint(0, max(live // 2, 1))) if live else 0
        kpos[b, pad:live] = np.arange(live - pad)
        lengths[b], starts[b] = live, pad
        q_pos[b] = live - pad - 1 if live else -1
    if share:
        kpos[B - 1] = kpos[2]
        lengths[B - 1], starts[B - 1] = lengths[2], starts[2]
        q_pos[B - 1] = q_pos[2]
    return (q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(q_pos),
            jnp.asarray(kpos), jnp.asarray(lengths), jnp.asarray(starts))


@pytest.mark.parametrize("B,Hq,Hkv,S,D,bs", [
    (4, 4, 2, 64, 16, 16),        # GQA 2x, aligned
    (3, 8, 1, 48, 8, 16),         # MQA
    (4, 4, 4, 33, 16, 8),         # MHA, non-block-aligned logical width
    (3, 6, 3, 40, 32, 8),         # GQA 2x
])
@pytest.mark.parametrize("window", [0, 16])
def test_paged_kernel_matches_gathered_dense(B, Hq, Hkv, S, D, bs, window):
    q, kp, vp, table, q_pos, kpos, lengths, starts = _paged_case(
        B, Hq, Hkv, S, D, bs, seed=S + D + bs)
    Sr = table.shape[1] * bs              # block-rounded physical width
    kd = gather_paged_kv(kp, table)
    vd = gather_paged_kv(vp, table)
    kpos_r = jnp.pad(kpos, ((0, 0), (0, Sr - S)), constant_values=-1)
    want = decode_attention(q, kd, vd, q_pos, kpos_r, lengths, starts=starts,
                            window=window, impl="naive")
    got = paged_decode_attention(q, kp, vp, table, q_pos, kpos, lengths,
                                 starts=starts, window=window,
                                 impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    # the gather-and-defer fallback is the same oracle by construction
    blk = paged_decode_attention(q, kp, vp, table, q_pos, kpos, lengths,
                                 starts=starts, window=window, impl="blocked")
    np.testing.assert_allclose(np.asarray(blk), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_paged_kernel_shared_and_sink_blocks():
    """Aliased tables (two rows reading the same physical blocks — the CoW
    sharing read pattern) and sink-redirected rows (freed slots) both
    match the gathered oracle; the empty row attends to nothing."""
    B, Hq, Hkv, S, D, bs = 5, 4, 2, 32, 16, 8
    q, kp, vp, table, q_pos, kpos, lengths, starts = _paged_case(
        B, Hq, Hkv, S, D, bs, seed=3, share=True)
    # row 0 is empty (length 0): point its table at the sink like a freed
    # serving slot — attention must not read through it
    table = table.at[0].set(0)
    kd = gather_paged_kv(kp, table)
    vd = gather_paged_kv(vp, table)
    want = decode_attention(q, kd, vd, q_pos, kpos, lengths, starts=starts,
                            impl="naive")
    got = paged_decode_attention(q, kp, vp, table, q_pos, kpos, lengths,
                                 starts=starts, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    # identical queries through aliased tables see identical contexts
    qs = q.at[B - 1].set(q[2])
    alias = paged_decode_attention(qs, kp, vp, table, q_pos, kpos, lengths,
                                   starts=starts, impl="interpret")
    np.testing.assert_array_equal(np.asarray(alias[B - 1]),
                                  np.asarray(alias[2]))
    assert bool(jnp.all(jnp.isfinite(got)))


def test_paged_gather_matches_take():
    rng = np.random.RandomState(0)
    NB, X, D, R, nb = 13, 6, 16, 4, 3
    pool = jnp.asarray(rng.randn(NB, X, D).astype(np.float32))
    table = jnp.asarray(rng.randint(0, NB, size=(R, nb)).astype(np.int32))
    want = jnp.take(pool, table.reshape(-1), axis=0).reshape(R, nb, X, D)
    got = paged_gather(pool, table, impl="interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ref = paged_gather(pool, table, impl="ref")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(want))


@pytest.mark.parametrize("gqa", [True, False])
def test_paged_slot_write_roundtrip(gqa):
    """Dense rows cut into blocks and scattered through their tables, then
    gathered back: the round trip is the identity on the written rows and
    every other pool block is untouched."""
    rng = np.random.RandomState(1)
    run, NB, Hkv, bs, D, R, nb = 2, 11, 2, 4, 8, 3, 2
    shape = (run, NB, Hkv, bs, D) if gqa else (run, NB, bs, D)
    pool = jnp.asarray(rng.randn(*shape).astype(np.float32))
    src_shape = (run, R, Hkv, nb * bs, D) if gqa else (run, R, nb * bs, D)
    src = jnp.asarray(rng.randn(*src_shape).astype(np.float32))
    # disjoint non-sink blocks per row
    blocks = rng.permutation(NB - 1)[:R * nb] + 1
    tables = jnp.asarray(
        np.broadcast_to(blocks.reshape(R, nb), (run, R, nb)).astype(np.int32))
    out = paged_slot_write(pool, src, tables, impl="interpret")
    flat = np.asarray(out)
    for r in range(R):
        got = np.take(np.asarray(out)[0], np.asarray(tables)[0, r], axis=0)
        if gqa:
            want = np.asarray(src)[0, r].reshape(Hkv, nb, bs, D) \
                .transpose(1, 0, 2, 3)
        else:
            want = np.asarray(src)[0, r].reshape(nb, bs, D)
        np.testing.assert_array_equal(got, want)
    untouched = sorted(set(range(NB)) - set(blocks.tolist()))
    np.testing.assert_array_equal(flat[:, untouched],
                                  np.asarray(pool)[:, untouched])


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("window", [0, 16])
def test_paged_kernel_reads_stacked_pools_in_place(layer, window):
    """Stacked pools (L=3) read at layer l give the same bits as the
    kernel on that layer's pools."""
    L = 3
    q, kp, vp, table, q_pos, kpos, lengths, starts = _paged_case(
        4, 4, 2, 64, 16, 16, seed=7)
    ks = jax.random.split(jax.random.PRNGKey(layer), 2)
    kp_st = jax.random.normal(ks[0], (L,) + kp.shape).at[layer].set(kp)
    vp_st = jax.random.normal(ks[1], (L,) + vp.shape).at[layer].set(vp)
    want = paged_decode_attention(q, kp, vp, table, q_pos, kpos, lengths,
                                  starts=starts, window=window,
                                  impl="interpret")
    got = paged_decode_attention(q, kp_st, vp_st, table, q_pos, kpos,
                                 lengths, starts, jnp.int32(layer),
                                 window=window, impl="interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
