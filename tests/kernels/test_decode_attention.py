"""decode_attention kernel: interpret-mode split-K sweep vs the jnp oracle
across GQA/MQA ratios, sliding windows and per-row live lengths (empty rows,
rows at S-1, mixed depths), plus agreement with the legacy naive decode.
Widths of 384, 768, 1280 and 1024 slots give the kernel 128-, 256- and
512-slot tiles over all KV heads of a row (``decode_block_k``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels
from repro.kernels.decode_attention.kernel import COUNTS
from repro.kernels.decode_attention.ops import decode_attention, decode_plan
from repro.kernels.decode_attention.ref import (decode_attention_blocked,
                                                decode_attention_ref)


def _case(B, Hq, Hkv, S, D, Dv=None, seed=0):
    """Decode-shaped inputs with mixed per-row cache depths.

    Row b's cache holds a left-padded context: pad_b slots of -1, then
    positions [0, live_b - pad_b).  lengths[b] = live_b is the row's live
    extent and starts[b] = pad_b its first live slot; slots outside
    [starts, lengths) carry pos = -1 (the cache contract)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, Hq, 1, D))
    k = jax.random.normal(ks[1], (B, Hkv, S, D))
    v = jax.random.normal(ks[2], (B, Hkv, S, D if Dv is None else Dv))
    rng = np.random.RandomState(seed)
    lengths = np.zeros(B, np.int32)
    starts = np.zeros(B, np.int32)
    q_pos = np.zeros(B, np.int32)
    kpos = np.full((B, S), -1, np.int32)
    for b in range(B):
        if b == 0:
            live = 0                      # empty cache row
        elif b == 1:
            live = S                      # row at the full cache width
        else:
            live = int(rng.randint(1, S))
        pad = int(rng.randint(0, max(live // 2, 1))) if live else 0
        kpos[b, pad:live] = np.arange(live - pad)
        lengths[b] = live
        starts[b] = pad
        q_pos[b] = live - pad - 1 if live else -1
    return (q, k, v, jnp.asarray(q_pos), jnp.asarray(kpos),
            jnp.asarray(lengths), jnp.asarray(starts))


@pytest.mark.parametrize("B,Hq,Hkv,S,D", [
    (4, 4, 2, 64, 16),          # GQA 2x
    (3, 8, 1, 48, 8),           # MQA
    (3, 4, 4, 33, 16),          # MHA, non-divisible S
    (4, 6, 3, 96, 32),          # GQA 2x, wider
    (3, 2, 1, 384, 16),         # MQA, three 128-slot tiles
    (3, 4, 2, 768, 16),         # GQA 2x, three 256-slot tiles
    (3, 16, 8, 1280, 16),       # qwen3's 16/8 heads, five 256-slot tiles
    (3, 16, 8, 1024, 16),       # two 512-slot tiles
])
@pytest.mark.parametrize("window", [0, 16])
def test_split_k_matches_ref(B, Hq, Hkv, S, D, window):
    q, k, v, q_pos, kpos, lengths, starts = _case(B, Hq, Hkv, S, D, seed=S + D)
    want = decode_attention_ref(q, k, v, q_pos, kpos, lengths, window=window)
    got = decode_attention(q, k, v, q_pos, kpos, lengths, window=window,
                           impl="interpret", block_k=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    blk = decode_attention(q, k, v, q_pos, kpos, lengths, window=window,
                           impl="blocked", block_k=16)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_empty_rows_are_exact_zero():
    q, k, v, q_pos, kpos, lengths, starts = _case(4, 4, 2, 40, 16, seed=3)
    for impl in ("naive", "blocked", "interpret"):
        out = np.asarray(decode_attention(q, k, v, q_pos, kpos, lengths,
                                          impl=impl, block_k=16))
        assert (out[0] == 0.0).all(), impl         # lengths[0] == 0


def test_lengths_none_defaults_to_full_width():
    q, k, v, q_pos, kpos, _, _ = _case(3, 4, 2, 40, 16, seed=5)
    want = decode_attention_ref(q, k, v, q_pos, kpos, None)
    for impl in ("blocked", "interpret"):
        got = decode_attention(q, k, v, q_pos, kpos, None, impl=impl,
                               block_k=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_lengths_are_authoritative():
    """A lengths bound tighter than the pos pattern masks the tail — every
    impl agrees, so a wrong (too small) bound can never desynchronise them."""
    B, Hq, Hkv, S, D = 2, 4, 2, 48, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, Hq, 1, D))
    k = jax.random.normal(ks[1], (B, Hkv, S, D))
    v = jax.random.normal(ks[2], (B, Hkv, S, D))
    kpos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    q_pos = jnp.full((B,), S - 1, jnp.int32)
    lengths = jnp.array([S, 17], jnp.int32)       # row 1: live slots ignored
    want = decode_attention_ref(q, k, v, q_pos, kpos, lengths)
    full = decode_attention_ref(q, k, v, q_pos, kpos, None)
    assert not np.allclose(np.asarray(want[1]), np.asarray(full[1]))
    for impl in ("blocked", "interpret"):
        got = decode_attention(q, k, v, q_pos, kpos, lengths, impl=impl,
                               block_k=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_mla_shaped_distinct_kv_dims():
    """G = 1 (MHA after MLA decompression) with Dk != Dv."""
    q, k, v, q_pos, kpos, lengths, starts = _case(3, 4, 4, 40, 24, Dv=16, seed=7)
    want = decode_attention_ref(q, k, v, q_pos, kpos, lengths)
    for impl in ("blocked", "interpret"):
        got = decode_attention(q, k, v, q_pos, kpos, lengths, impl=impl,
                               block_k=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_naive_impl_matches_legacy_decode_bitwise():
    """impl='naive' through the op == dot_product_attention at T=1, bit for
    bit: routing decode through the op keeps the legacy path reproducible."""
    from repro.models.attention import dot_product_attention
    q, k, v, q_pos, kpos, lengths, starts = _case(4, 4, 2, 40, 16, seed=11)
    legacy = dot_product_attention(q, k, v, q_pos[:, None], kpos)
    got = decode_attention(q, k, v, q_pos, kpos, lengths, impl="naive")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(legacy))


def test_single_split_degenerate():
    """block_k >= S: one split; the combine stage must be an identity."""
    q, k, v, q_pos, kpos, lengths, starts = _case(3, 4, 2, 24, 16, seed=13)
    want = decode_attention_ref(q, k, v, q_pos, kpos, lengths)
    got = decode_attention(q, k, v, q_pos, kpos, lengths, impl="interpret",
                           block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("window", [0, 16])
def test_starts_skip_dead_left_padding(window):
    """Per-row start bounds (resume-shaped: dead left pad before the
    compacted context) agree across every impl."""
    q, k, v, q_pos, kpos, lengths, starts = _case(4, 4, 2, 64, 16, seed=19)
    want = decode_attention_ref(q, k, v, q_pos, kpos, lengths, starts,
                                window=window)
    # starts bound == the pos mask it mirrors, so it changes nothing...
    base = decode_attention_ref(q, k, v, q_pos, kpos, lengths, window=window)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(base))
    for impl in ("naive", "blocked", "interpret"):
        got = decode_attention(q, k, v, q_pos, kpos, lengths, starts,
                               window=window, impl=impl, block_k=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_starts_are_authoritative():
    """...but a start bound tighter than the pos pattern masks the head,
    and every impl still agrees (same contract as lengths)."""
    q, k, v, q_pos, kpos, lengths, starts = _case(4, 4, 2, 64, 16, seed=23)
    tight = jnp.minimum(starts + 11, lengths)
    want = decode_attention_ref(q, k, v, q_pos, kpos, lengths, tight)
    base = decode_attention_ref(q, k, v, q_pos, kpos, lengths, starts)
    assert not np.allclose(np.asarray(want), np.asarray(base))
    for impl in ("blocked", "interpret"):
        got = decode_attention(q, k, v, q_pos, kpos, lengths, tight,
                               impl=impl, block_k=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_row_budget_independence():
    """Garbage K/V outside each row's live range never leaks into outputs."""
    q, k, v, q_pos, kpos, lengths, starts = _case(4, 4, 2, 64, 16, seed=17)
    dead = ((jnp.arange(64) >= lengths[:, None])
            | (jnp.arange(64) < starts[:, None]))[:, None, :, None]
    k2 = jnp.where(dead, 999.0, k)
    v2 = jnp.where(dead, -999.0, v)
    for impl in ("blocked", "interpret"):
        a = decode_attention(q, k, v, q_pos, kpos, lengths, starts,
                             impl=impl, block_k=16)
        b = decode_attention(q, k2, v2, q_pos, kpos, lengths, starts,
                             impl=impl, block_k=16)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------- multi-token blocks (§9)


def _block_case(B, Hq, Hkv, S, D, T, Dv=None, seed=0):
    """Draft-verify-shaped inputs: per row, a contiguous live context of
    ctx_b tokens followed by a written block of qlen_b <= T query tokens at
    consecutive positions; block columns t >= qlen_b carry q_pos = -1 and
    their cache slots pos = -1 (draft padding)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, Hq, T, D))
    k = jax.random.normal(ks[1], (B, Hkv, S, D))
    v = jax.random.normal(ks[2], (B, Hkv, S, D if Dv is None else Dv))
    rng = np.random.RandomState(seed)
    lengths = np.zeros(B, np.int32)
    starts = np.zeros(B, np.int32)
    q_pos = np.full((B, T), -1, np.int32)
    kpos = np.full((B, S), -1, np.int32)
    for b in range(B):
        ctx = int(rng.randint(1, S - T))
        pad = int(rng.randint(0, ctx))
        if b == 0:
            qlen = 0                      # done row: no live queries
        elif b == 1:
            qlen = T                      # full draft block
        else:
            qlen = int(rng.randint(1, T + 1))
        kpos[b, pad:ctx] = np.arange(ctx - pad)
        kpos[b, ctx:ctx + qlen] = np.arange(ctx - pad, ctx - pad + qlen)
        q_pos[b, :qlen] = np.arange(ctx - pad, ctx - pad + qlen)
        lengths[b] = ctx + T              # block bound incl. padded slots
        starts[b] = pad
    return (q, k, v, jnp.asarray(q_pos), jnp.asarray(kpos),
            jnp.asarray(lengths), jnp.asarray(starts))


@pytest.mark.parametrize("B,Hq,Hkv,S,D,T", [
    (4, 4, 2, 64, 16, 5),       # GQA 2x, draft_k = 4
    (3, 8, 1, 48, 8, 3),        # MQA
    (3, 4, 4, 40, 16, 2),       # MHA
    (3, 2, 1, 384, 16, 5),      # MQA, 128-slot tiles
    (3, 16, 8, 1280, 16, 5),    # qwen3's 16/8 heads, 256-slot tiles
    (3, 4, 2, 1024, 16, 5),     # 512-slot tiles
])
@pytest.mark.parametrize("window", [0, 16])
def test_block_query_matches_ref(B, Hq, Hkv, S, D, T, window):
    """T-token blocks: interpret-mode kernel == naive oracle == blocked."""
    q, k, v, q_pos, kpos, lengths, starts = _block_case(B, Hq, Hkv, S, D, T,
                                                        seed=S + D + T)
    want = decode_attention_ref(q, k, v, q_pos, kpos, lengths, starts,
                                window=window)
    for impl in ("blocked", "interpret"):
        got = decode_attention(q, k, v, q_pos, kpos, lengths, starts,
                               window=window, impl=impl, block_k=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_block_query_causal_within_block():
    """Query t must not see block tokens written after it: perturbing slot
    t+1's K/V leaves query t's output bit-unchanged on every impl."""
    B, Hq, Hkv, S, D, T = 2, 4, 2, 48, 16, 4
    q, k, v, q_pos, kpos, lengths, starts = _block_case(
        B, Hq, Hkv, S, D, T, seed=3)
    # poke the LAST block slot of row 1 (qlen == T there by construction)
    last = int(np.asarray(lengths)[1]) - 1
    k2 = k.at[1, :, last].set(123.0)
    v2 = v.at[1, :, last].set(-123.0)
    for impl in ("naive", "blocked", "interpret"):
        a = decode_attention(q, k, v, q_pos, kpos, lengths, starts,
                             impl=impl, block_k=16)
        b2 = decode_attention(q, k2, v2, q_pos, kpos, lengths, starts,
                              impl=impl, block_k=16)
        np.testing.assert_array_equal(np.asarray(a[:, :, :T - 1]),
                                      np.asarray(b2[:, :, :T - 1]))
        assert not np.allclose(np.asarray(a[1, :, T - 1]),
                               np.asarray(b2[1, :, T - 1]))


def test_block_query_mla_shapes():
    """Dk != Dv with a multi-token block (MLA drafting)."""
    q, k, v, q_pos, kpos, lengths, starts = _block_case(
        3, 4, 4, 40, 24, 3, Dv=16, seed=11)
    want = decode_attention_ref(q, k, v, q_pos, kpos, lengths, starts)
    for impl in ("blocked", "interpret"):
        got = decode_attention(q, k, v, q_pos, kpos, lengths, starts,
                               impl=impl, block_k=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_block_query_t1_matches_legacy_shapes():
    """A (B, T=1) position array is the same call as the legacy (B,) one."""
    q, k, v, q_pos, kpos, lengths, starts = _case(4, 4, 2, 64, 16, seed=29)
    for impl in ("naive", "blocked", "interpret"):
        a = decode_attention(q, k, v, q_pos, kpos, lengths, starts,
                             impl=impl, block_k=16)
        b = decode_attention(q, k, v, q_pos[:, None], kpos, lengths, starts,
                             impl=impl, block_k=16)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _pad_operand_shapes(fn, *args):
    """Operand shapes of every ``pad`` in fn's jaxpr, nested ones too."""
    shapes = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pad":
                shapes.append(tuple(eqn.invars[0].aval.shape))
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return shapes


@pytest.mark.parametrize("S", [1280, 202, 1024])
@pytest.mark.parametrize("T", [1, 5])
@pytest.mark.parametrize("window", [0, 16])
@pytest.mark.parametrize("layer", [0, 2, 4])
def test_stacked_layer_read_is_bit_identical(S, T, window, layer):
    """The kernel on a stacked cache (L=5) at layer l gives the same bits as
    the kernel on that layer's slice: first, middle and last layer, decode
    and draft blocks, widths of whole 256- and 512-slot tiles (1280, 1024)
    and a trainer width that is not (202, whose padding must touch one
    layer, not the stack)."""
    L = 5
    q, k, v, q_pos, kpos, lengths, starts = _block_case(3, 4, 2, S, 16, T,
                                                        seed=S + T)
    ks = jax.random.split(jax.random.PRNGKey(layer), 2)
    k_st = jax.random.normal(ks[0], (L,) + k.shape).at[layer].set(k)
    v_st = jax.random.normal(ks[1], (L,) + v.shape).at[layer].set(v)
    want = decode_attention(q, k, v, q_pos, kpos, lengths, starts,
                            window=window, impl="interpret")

    def stacked(k_st, v_st, layer):
        return decode_attention(q, k_st, v_st, q_pos, kpos, lengths, starts,
                                layer, window=window, impl="interpret")
    got = stacked(k_st, v_st, jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    pads = _pad_operand_shapes(stacked, k_st, v_st, jnp.int32(layer))
    assert all(s[0] == 1 for s in pads if len(s) == 5), pads
    assert bool(pads) == (S % 128 != 0)


# ------------------------------------------------------- tiles (DESIGN.md §7)


@pytest.mark.parametrize("S,Hkv,D,itemsize,want", [
    (1280, 8, 128, 2, 256),     # q06b-spec-reuse: 512 does not divide 1280
    (768, 8, 128, 2, 256),      # the first-epoch cells
    (1024, 8, 128, 2, 512),     # 512 slots x 8 heads x 2 x 128 x bf16: 2 MiB
    (384, 8, 128, 2, 128),
    (4096, 16, 128, 2, 256),    # 512 would hold 4 MiB
    (4096, 64, 128, 2, 128),    # even 128 is over the budget: the floor
    (1024, 8, 128, 4, 256),     # float32 caches take half the slots
    (1024, 1, 16, 4, 512),
    (202, 8, 128, 2, 128),      # not whole 128-slot tiles: padded
    (64, 2, 16, 4, 64),         # one short tile
])
def test_tile_is_the_widest_that_divides_and_fits(S, Hkv, D, itemsize, want):
    from repro.kernels.decode_attention.kernel import (KV_BLOCK_BYTES,
                                                       TILE_WIDTHS,
                                                       decode_block_k)
    bk = decode_block_k(S, Hkv, D, D, itemsize)
    assert bk == want
    if bk > 128:
        assert S % bk == 0
        assert bk * Hkv * 2 * D * itemsize <= KV_BLOCK_BYTES
        wider = [w for w in TILE_WIDTHS if w > bk and S % w == 0]
        assert all(w * Hkv * 2 * D * itemsize > KV_BLOCK_BYTES
                   for w in wider)


@pytest.mark.parametrize("block_k,nsplit", [(256, 5), (128, 6), (512, 2)])
def test_dead_splits_read_the_nearest_live_tile(block_k, nsplit):
    """Every (start, length, q_len) of a row: a live split reads its own
    tile; a dead split in front of the live range reads the first live
    tile and one behind it the last, so no dead split reads a tile that no
    live split reads (tile 0 only where it is live).  A row with no live
    tile reads one tile in every split."""
    from repro.kernels.decode_attention.kernel import _kv_tile, _split_live
    S = block_k * nsplit
    edges = sorted({0, 1, block_k - 1, block_k, block_k + 1, S // 2,
                    S - block_k, S - 1, S})
    s = jnp.arange(nsplit, dtype=jnp.int32)
    for start in edges:
        for length in edges:
            for qlen in (0, 1):
                live = np.asarray(_split_live(s, block_k, length, start,
                                              qlen))
                tile = np.asarray(_kv_tile(s, block_k, nsplit, length,
                                           start, qlen))
                assert ((0 <= tile) & (tile < nsplit)).all()
                if not live.any():
                    assert len(set(tile.tolist())) == 1
                    continue
                lo, hi = np.flatnonzero(live)[[0, -1]]
                np.testing.assert_array_equal(tile, np.clip(np.arange(
                    nsplit), lo, hi))
                assert live[tile].all()


def test_qwen3_06b_decode_step_takes_256_slot_tiles():
    """A decode step at qwen3-0.6b's attention widths (16/8 heads of 128,
    bf16) over the reuse cell's 1280-slot cache: every layer's dense kernel
    call takes 256-slot tiles."""
    from repro.configs import get_config
    from repro.models import model as M
    cfg = get_config("qwen3-0.6b").replace(num_layers=2, vocab_size=256,
                                           decode_impl="interpret")
    params = jax.eval_shape(lambda k: M.init_lm(k, cfg),
                            jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: M.init_cache(cfg, 8, 1280))
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    COUNTS.clear()
    jax.eval_shape(lambda p, t, c: M.decode_step(p, cfg, t, t, c, 768),
                   params, tok, caches)
    counts = COUNTS.copy()
    assert counts["decode_attn_tile_256"] == counts["decode_attn_inplace"]
    assert counts["decode_attn_tile_256"] > 0
    assert counts["decode_attn_tile_128"] == counts["decode_attn_tile_512"] == 0


@pytest.mark.parametrize("impl,ref,want", [
    ("auto", "naive", "naive"),
    ("auto", "ref", "ref"),
    ("pallas", "ref", "pallas"),
    ("interpret", "naive", "interpret"),
    ("blocked", "naive", "blocked"),
])
def test_resolve_off_the_tpu(impl, ref, want):
    """Off a TPU, "auto" is the op's reference; a named impl is itself."""
    assert kernels.resolve("decode_attention", impl, ref) == want


# backend, requested impl, cache width, paged, mesh, latent -> the concrete
# impl and whether the stacked cache is read in place
@pytest.mark.parametrize("backend,impl,S,paged,mesh,latent,want", [
    # TPU, GQA: the dense kernel in place on whole 128-slot tiles ...
    ("tpu", "auto", 1280, False, False, False, ("pallas", True)),
    # ... on a padded layer slice at a ragged width, or under a mesh
    ("tpu", "auto", 202, False, False, False, ("pallas", False)),
    ("tpu", "auto", 1280, False, True, False, ("pallas", False)),
    # paged "auto": the gathered dense view, read by the dense kernel
    ("tpu", "auto", 1280, True, False, False, ("pallas", False)),
    # the paged kernel runs, in place, only when named
    ("tpu", "pallas", 1280, True, False, False, ("pallas", True)),
    ("tpu", "pallas", 1280, True, True, False, ("pallas", False)),
    ("tpu", "naive", 1280, False, False, False, ("naive", False)),
    ("tpu", "blocked", 1280, False, False, False, ("blocked", False)),
    # TPU, MLA: the latent kernel only in place; jnp everywhere else
    ("tpu", "auto", 1280, False, False, True, ("pallas", True)),
    ("tpu", "auto", 300, False, False, True, ("naive", False)),
    ("tpu", "auto", 1280, True, False, True, ("naive", False)),
    ("tpu", "auto", 1280, False, True, True, ("naive", False)),
    # CPU: "auto" is jnp, naive up to NAIVE_MAX_S slots, blocked beyond
    ("cpu", "auto", 64, False, False, False, ("naive", False)),
    ("cpu", "auto", 1280, False, False, False, ("blocked", False)),
    ("cpu", "auto", 1280, True, False, False, ("blocked", False)),
    ("cpu", "auto", 1280, False, False, True, ("naive", False)),
    # CPU, a kernel named in interpret mode takes the TPU's paths
    ("cpu", "interpret", 1280, False, False, False, ("interpret", True)),
    ("cpu", "interpret", 202, False, False, False, ("interpret", False)),
    ("cpu", "interpret", 64, True, False, False, ("interpret", True)),
    ("cpu", "interpret", 64, False, True, False, ("interpret", False)),
    ("cpu", "interpret", 256, False, False, True, ("interpret", True)),
    ("cpu", "interpret", 300, False, False, True, ("naive", False)),
    ("cpu", "blocked", 1280, False, False, True, ("naive", False)),
])
def test_decode_plan(monkeypatch, backend, impl, S, paged, mesh, latent,
                     want):
    """The decode dispatch's decision table, with the resolver patched to
    stand for the backend; each call is counted on its path."""
    real = kernels.resolve
    monkeypatch.setattr(kernels, "resolve", lambda kernel, i, ref: (
        "pallas" if i == "auto" and backend == "tpu"
        else real(kernel, i, ref)))
    COUNTS.clear()
    assert decode_plan(impl, width=S, paged=paged, mesh=mesh,
                       latent=latent) == want
    path = "decode_attn_inplace" if want[1] else "decode_attn_sliced"
    assert COUNTS == {path: 1}


# ----------------------------------------------------------------- latent


def _mla_case(S, T, layer, seed, B=3, H=4, r=32, rd=8, nd=16, vd=16, L=5):
    """Absorbed and decompressed operands of one MLA decode call over a
    stacked latent cache (L layers), with _block_case's per-row masking
    (a done row, a full block, left padding, live bounds)."""
    _, _, _, q_pos, kpos, lengths, starts = _block_case(B, 1, 1, S, 8, T,
                                                        seed=seed)
    ks = jax.random.split(jax.random.PRNGKey(seed + layer), 5)
    q_nope = jax.random.normal(ks[0], (B, H, T, nd))
    q_rope = jax.random.normal(ks[1], (B, H, T, rd))
    ckv = jax.random.normal(ks[2], (L, B, S, r))
    krope = jax.random.normal(ks[3], (L, B, S, rd))
    w = jax.random.normal(ks[4], (r, H, nd + vd)) * r ** -0.5
    q_lat = jnp.einsum("bhtn,rhn->bhtr", q_nope, w[..., :nd])
    return (q_nope, q_rope, q_lat, ckv, krope, w, q_pos, kpos, lengths,
            starts)


def _decompressed(q_nope, q_rope, ckv, krope, w, q_pos, kpos, lengths,
                  starts, nd=16):
    """Textbook MLA over one layer's latent: per-head K = [ckv W_UK, k_pe],
    V = ckv W_UV, through the GQA oracle (scale (nd + rd)^-0.5)."""
    H = q_nope.shape[1]
    k = jnp.concatenate([jnp.einsum("bsr,rhn->bhsn", ckv, w[..., :nd]),
                         jnp.broadcast_to(krope[:, None], (ckv.shape[0], H)
                                          + krope.shape[1:])], -1)
    v = jnp.einsum("bsr,rhv->bhsv", ckv, w[..., nd:])
    return decode_attention_ref(jnp.concatenate([q_nope, q_rope], -1), k, v,
                                q_pos, kpos, lengths, starts)


@pytest.mark.parametrize("S,block_k", [(1280, 256), (1000, 40)])
@pytest.mark.parametrize("T", [1, 3])
@pytest.mark.parametrize("layer", [0, 2, 4])
def test_mla_kernel_matches_absorbed_and_decompressed(S, block_k, T, layer):
    """``decode_attention_mla`` in interpret mode reading a stacked latent
    cache at its first, middle and last layer, against the jnp absorbed form
    on that layer's slice and the decompressed textbook form: a width of
    whole 256-slot tiles, and one (1000) that the model routes to the jnp
    form (its kernel run here on 40-slot tiles)."""
    from repro.kernels.decode_attention.ops import decode_attention_mla
    (q_nope, q_rope, q_lat, ckv, krope, w, q_pos, kpos, lengths,
     starts) = _mla_case(S, T, layer, seed=S + T)
    scale = 24 ** -0.5
    got = decode_attention_mla(q_lat, q_rope, ckv, krope, q_pos, kpos,
                               lengths, starts, jnp.int32(layer),
                               scale=scale, impl="interpret",
                               block_k=block_k)
    jnp_form = decode_attention_mla(q_lat, q_rope, ckv[layer], krope[layer],
                                    q_pos, kpos, lengths, starts,
                                    scale=scale, impl="naive")
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp_form),
                               atol=2e-5, rtol=2e-5)
    out = jnp.einsum("bhtr,rhv->bhtv", got, w[..., 16:])
    want = _decompressed(q_nope, q_rope, ckv[layer], krope[layer], w, q_pos,
                         kpos, lengths, starts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-4,
                               rtol=2e-4)
    assert (np.asarray(got)[0] == 0.0).all()       # the done row
