"""Attention semantics: GQA grouping, sliding window, qk-norm/bias, MLA."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (apply_gqa, dot_product_attention,
                                    make_gqa, make_mla, apply_mla)
from repro.models.config import ModelConfig


def _pos(B, T):
    return jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))


def test_gqa_equals_repeated_mha():
    """GQA(kv=2) == MHA with kv heads physically repeated."""
    B, Hq, Hkv, T, D = 2, 4, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, T, D))
    k = jax.random.normal(ks[1], (B, Hkv, T, D))
    v = jax.random.normal(ks[2], (B, Hkv, T, D))
    pos = _pos(B, T)
    out_gqa = dot_product_attention(q, k, v, pos, pos)
    k_rep = jnp.repeat(k, Hq // Hkv, axis=1)
    v_rep = jnp.repeat(v, Hq // Hkv, axis=1)
    out_mha = dot_product_attention(q, k_rep, v_rep, pos, pos)
    np.testing.assert_allclose(np.asarray(out_gqa), np.asarray(out_mha),
                               atol=1e-5)


def test_sliding_window_masks_old_tokens():
    """A key outside the window must not influence the output."""
    B, H, T, D = 1, 1, 12, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, T, D))
    k = jax.random.normal(ks[1], (B, H, T, D))
    v = jax.random.normal(ks[2], (B, H, T, D))
    pos = _pos(B, T)
    W = 4
    out = dot_product_attention(q, k, v, pos, pos, window=W)
    # perturb key/value at position 0: outputs at t >= W must be unchanged
    k2 = k.at[:, :, 0].add(100.0)
    v2 = v.at[:, :, 0].add(100.0)
    out2 = dot_product_attention(q, k2, v2, pos, pos, window=W)
    np.testing.assert_allclose(np.asarray(out[:, :, W:]),
                               np.asarray(out2[:, :, W:]), atol=1e-5)
    assert not np.allclose(np.asarray(out[:, :, :W]),
                           np.asarray(out2[:, :, :W]))


def test_padding_rows_ignored():
    """Keys at position -1 never contribute."""
    B, H, T, D = 1, 2, 10, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, T, D))
    k = jax.random.normal(ks[1], (B, H, T, D))
    v = jax.random.normal(ks[2], (B, H, T, D))
    pos = _pos(B, T)
    pos_padded = pos.at[:, :3].set(-1)
    out_a = dot_product_attention(q, k, v, pos_padded, pos_padded)
    k2 = k.at[:, :, :3].set(999.0)
    v2 = v.at[:, :, :3].set(-999.0)
    out_b = dot_product_attention(q, k2, v2, pos_padded, pos_padded)
    np.testing.assert_allclose(np.asarray(out_a[:, :, 3:]),
                               np.asarray(out_b[:, :, 3:]), atol=1e-4)


def test_causality():
    """Future keys never influence current outputs."""
    B, H, T, D = 1, 1, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, T, D))
    k = jax.random.normal(ks[1], (B, H, T, D))
    v = jax.random.normal(ks[2], (B, H, T, D))
    pos = _pos(B, T)
    out = dot_product_attention(q, k, v, pos, pos)
    k2 = k.at[:, :, -1].add(50.0)
    out2 = dot_product_attention(q, k2, v, pos, pos)
    np.testing.assert_allclose(np.asarray(out[:, :, :-1]),
                               np.asarray(out2[:, :, :-1]), atol=1e-5)


def test_mla_cache_decompression_matches_full(tiny_cfg):
    """MLA with latent cache == MLA recomputed from scratch."""
    cfg = tiny_cfg.replace(attention_kind="mla", q_lora_rank=32,
                           kv_lora_rank=32, qk_nope_head_dim=16,
                           qk_rope_head_dim=8, v_head_dim=16)
    p = make_mla(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, T = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.d_model))
    pos = _pos(B, T)
    full, _ = apply_mla(p, cfg, x, pos)
    from repro.models.attention import init_kv_cache
    cache = jax.tree.map(lambda a: a[None],       # a stack of one layer
                         init_kv_cache(cfg, B, T, jnp.float32))
    via_cache, _ = apply_mla(p, cfg, x, pos, cache=cache, cache_start=0,
                             layer=0)
    np.testing.assert_allclose(np.asarray(full), np.asarray(via_cache),
                               atol=1e-5)


def test_qkv_bias_changes_output(tiny_cfg):
    cfg_nb = tiny_cfg
    cfg_b = tiny_cfg.replace(qkv_bias=True)
    p = make_gqa(jax.random.PRNGKey(0), cfg_b, jnp.float32)
    assert "bias" in p["wq"]
    p["wq"]["bias"] = p["wq"]["bias"] + 1.0
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, cfg_b.d_model))
    pos = _pos(1, 8)
    out_b, _ = apply_gqa(p, cfg_b, x, pos)
    p0 = {k: (dict(v, bias=jnp.zeros_like(v["bias"])) if isinstance(v, dict)
              and "bias" in v else v) for k, v in p.items()}
    out_0, _ = apply_gqa(p0, cfg_b, x, pos)
    assert not np.allclose(np.asarray(out_b), np.asarray(out_0))
