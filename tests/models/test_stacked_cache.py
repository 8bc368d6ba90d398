"""The stacked K/V cache is written and read in place (DESIGN.md §3).

A layer writes its new K/V into its slot of the run's cache stack, and the
flash-decode kernel reads its layer out of the stack; every other decode
path reads one layer slice.  The kernel package's ``COUNTS`` records
which path each decode-attention call took, and both paths give the same
bits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.kernels.decode_attention.kernel import COUNTS
from repro.models import model as M
from repro.models.attention import _cache_write
from repro.models.config import ModelConfig

L, B = 3, 2


def _cfg(**kw):
    return ModelConfig(**{**dict(name="q", num_layers=L, d_model=64,
                                 num_heads=4, num_kv_heads=2, head_dim=16,
                                 d_ff=128, vocab_size=32, qk_norm=True), **kw})


def _decode_once(cfg, S, mesh=None):
    """Prefill 6 tokens into an S-wide cache, then one decode step; returns
    (COUNTS of the step, logits, caches), run op by op so the counters
    count every layer's call."""
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, 6), 3, 32)
    pos = jnp.broadcast_to(jnp.arange(6, dtype=jnp.int32), (B, 6))
    caches = M.init_cache(cfg, B, S)
    _, caches = M.prefill(params, cfg, prompt, pos, caches)
    tok = jnp.full((B, 1), 5, jnp.int32)
    with jax.disable_jit():
        COUNTS.clear()
        logits, caches = M.decode_step(params, cfg, tok,
                                       jnp.full((B, 1), 6, jnp.int32),
                                       caches, 6, mesh=mesh)
        counts = COUNTS.copy()
    return counts, logits, caches


def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def test_kernel_reads_the_stack_in_place_once_per_layer():
    counts, _, _ = _decode_once(_cfg(decode_impl="interpret"), 64)
    assert counts["decode_attn_inplace"] == L
    assert counts["decode_attn_sliced"] == 0


@pytest.mark.parametrize("impl,S,mesh", [
    ("naive", 64, False),          # jnp oracle
    ("blocked", 64, False),        # length-bounded jnp flash
    ("interpret", 202, False),     # not a whole number of kernel tiles
    ("interpret", 64, True),       # the mesh shard_map wrapper
])
def test_other_paths_read_one_layer_slice(impl, S, mesh):
    counts, _, _ = _decode_once(_cfg(decode_impl=impl), S,
                                _one_device_mesh() if mesh else None)
    assert counts["decode_attn_sliced"] == L
    assert counts["decode_attn_inplace"] == 0


def _mla_cfg():
    return _cfg(decode_impl="interpret", attention_kind="mla",
                q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, qk_norm=False)


def test_mla_decompresses_a_layer_slice():
    """MLA decode no longer decompresses anything: at a width that is not a
    whole number of 256-slot latent tiles it reads the layer's latent slice
    (the jnp absorbed form)."""
    counts, _, _ = _decode_once(_mla_cfg(), 300)
    assert counts["decode_attn_sliced"] == L
    assert counts["decode_attn_inplace"] == 0


@pytest.mark.parametrize("S", [64, 256])
def test_mla_latent_kernel_reads_the_stack_in_place(S):
    """At a width of whole latent tiles (one short tile, or 256 slots) the
    latent kernel reads each layer's latent out of the stack; the result
    is the jnp absorbed form's on the layer slices, to float32 rounding
    (the two sum the split partials in another order)."""
    counts, logits, caches = _decode_once(_mla_cfg(), S)
    assert counts["decode_attn_inplace"] == L
    assert counts["decode_attn_sliced"] == 0
    _, want, want_caches = _decode_once(_mla_cfg().replace(
        decode_impl="naive"), S)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(caches), jax.tree.leaves(want_caches)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-5)


def test_paged_kernel_reads_the_stacked_pools_in_place():
    counts, _, _ = _decode_once(
        _cfg(decode_impl="interpret", cache_layout="paged", kv_block_size=16),
        64)
    assert counts["decode_attn_inplace"] == L


def test_in_place_read_matches_the_slice_bit_for_bit():
    """The same kernel on the stack and on a layer slice (the mesh wrapper
    on one device slices): identical logits and caches."""
    cfg = _cfg(decode_impl="interpret")
    _, want_logits, want_caches = _decode_once(cfg, 64, _one_device_mesh())
    _, logits, caches = _decode_once(cfg, 64)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    for a, b in zip(jax.tree.leaves(caches), jax.tree.leaves(want_caches)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _layer_write(buf, update, start, axis):
    """One layer's write as a per-layer cache does it."""
    if jnp.ndim(start) == 0:
        return jax.lax.dynamic_update_slice_in_dim(buf, update, start, axis)
    return jax.vmap(
        lambda b, u, s: jax.lax.dynamic_update_slice_in_dim(b, u, s, axis)
    )(buf, update, start)


@pytest.mark.parametrize("start", [0, 5, 14, "rows"])
@pytest.mark.parametrize("layer", [0, 1, L - 1])
def test_stack_write_touches_one_layer_slot(start, layer):
    """Writing at [layer, ..., start] equals writing that layer's slice and
    leaves every other layer as it was: scalar starts (one clamped to the
    end) and per-row starts, on the K/V (axis -2) and pos (axis -1)
    leaves."""
    S, T = 16, 3
    start = jnp.array([2, 15], jnp.int32) if start == "rows" else start
    ks = jax.random.split(jax.random.PRNGKey(layer), 2)
    kv = jax.random.normal(ks[0], (L, B, 2, S, 4))
    upd = jax.random.normal(ks[1], (B, 2, T, 4))
    pos = jnp.full((L, B, S), -1, jnp.int32)
    upd_pos = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
    for buf, u, axis in ((kv, upd, -2), (pos, upd_pos, -1)):
        got = _cache_write(buf, u, start, layer, axis=axis)
        want = buf.at[layer].set(_layer_write(buf[layer], u, start, axis))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
