"""One-pass speculative rollout: equivalence with the two-pass path under a
fixed PRNG key, cache-compaction correctness against an aligned re-prefill,
and the no-second-prefill op-count guarantee."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RolloutCache, SpecConfig, rollout
from repro.core.verify import verify_and_prefill
from repro.engine.generate import GenerateConfig, positions_from_mask
from repro.models import model as M
from repro.models.config import ModelConfig

B, P, N = 4, 8, 12


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(name="t", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=32)
    # two different policies so verification produces real partial rejections
    params_a = M.init_lm(jax.random.PRNGKey(0), cfg)
    params_b = M.init_lm(jax.random.PRNGKey(42), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, P), 3, 32)
    mask = jnp.ones((B, P), bool)
    return cfg, params_a, params_b, prompt, mask


def _seeded_cache(cfg, params, prompt, mask, variant="spec"):
    cache = RolloutCache()
    spec = SpecConfig(variant=variant, one_pass="off")
    gen = GenerateConfig(max_new_tokens=N)
    rollout(params, cfg, gen, spec, prompt, mask, list(range(B)), cache,
            jax.random.PRNGKey(0), 0)
    return cache


@pytest.mark.parametrize("variant", ["spec", "delayed"])
def test_one_pass_matches_two_pass(setup, variant):
    """Same key => same rejection indices, tokens, lengths and logprobs."""
    cfg, params_a, params_b, prompt, mask = setup
    gen = GenerateConfig(max_new_tokens=N)
    ids = list(range(B))
    cache1 = _seeded_cache(cfg, params_a, prompt, mask)
    if variant == "delayed":   # lag=2 needs two cached visits
        spec = SpecConfig(variant="spec", one_pass="off")
        rollout(params_a, cfg, gen, spec, prompt, mask, ids, cache1,
                jax.random.PRNGKey(5), 1)
    cache2 = copy.deepcopy(cache1)

    key = jax.random.PRNGKey(7)
    two = rollout(params_b, cfg, gen,
                  SpecConfig(variant=variant, one_pass="off"),
                  prompt, mask, ids, cache1, key, 2)
    one = rollout(params_b, cfg, gen,
                  SpecConfig(variant=variant, one_pass="on"),
                  prompt, mask, ids, cache2, key, 2)

    assert one.metrics["one_pass"] == 1.0
    assert one.metrics["prefill_passes"] == 1.0
    assert two.metrics["prefill_passes"] == 2.0
    assert one.metrics["n_reused"] == two.metrics["n_reused"]
    np.testing.assert_array_equal(one.length, two.length)
    np.testing.assert_array_equal(one.response, two.response)
    np.testing.assert_allclose(one.behaviour_logprobs, two.behaviour_logprobs,
                               atol=1e-5, rtol=1e-5)
    assert one.metrics["n_reused"] > 0          # the comparison is non-trivial


def test_one_pass_with_pallas_compactor(setup, interpret_kernels):
    """Interpret-mode cache_gather kernel on the real rollout path."""
    cfg, params_a, params_b, prompt, mask = setup
    gen = GenerateConfig(max_new_tokens=N)
    ids = list(range(B))
    cache1 = _seeded_cache(cfg, params_a, prompt, mask)
    cache2 = copy.deepcopy(cache1)
    key = jax.random.PRNGKey(3)
    ref = rollout(params_b, cfg, gen,
                  SpecConfig(variant="spec", one_pass="on"),
                  prompt, mask, ids, cache1, key, 1)
    with interpret_kernels("cache_gather", "cache_slot_write"):
        ker = rollout(params_b, cfg, gen,
                      SpecConfig(variant="spec", one_pass="on"),
                      prompt, mask, ids, cache2, key, 1)
    np.testing.assert_array_equal(ker.response, ref.response)
    np.testing.assert_array_equal(ker.length, ref.length)


def test_realigned_cache_matches_aligned_prefill(setup):
    """Compacted verify caches == prefill over the left-aligned tokens:
    identical slot positions everywhere, identical K/V on valid slots."""
    cfg, params_a, params_b, prompt, mask = setup
    from repro.core.spec_rollout import left_align

    draft = jax.random.randint(jax.random.PRNGKey(9), (B, N), 3, 32)
    draft_len = jnp.array([0, 3, 7, N], jnp.int32)
    didx = jnp.arange(N)[None, :]
    draft_mask = didx < draft_len[:, None]
    draft_lp = jnp.where(draft_mask, -1.0, 0.0)

    ver = verify_and_prefill(params_a, cfg, prompt, mask, draft, draft_lp,
                             draft_len, jax.random.PRNGKey(2), 0.0)
    n = ver["n"]
    W = P + N
    p_len = mask.sum(axis=1).astype(jnp.int32)
    got = M.realign_decode_cache(cfg, ver["caches"], (N - n).astype(jnp.int32),
                                 p_len + n, W)

    # reference: left-align prompt ⊕ accepted prefix and prefill from scratch
    prefix_mask = didx < n[:, None]
    combined = jnp.concatenate([prompt, jnp.where(prefix_mask, draft, 0)], axis=1)
    combined_mask = jnp.concatenate([mask, prefix_mask], axis=1)
    al_tok, al_mask = left_align(combined, combined_mask)
    want_caches = M.init_cache(cfg, B, W + N)
    _, want_caches = M.prefill(params_a, cfg, al_tok,
                               positions_from_mask(al_mask), want_caches)

    for run_got, run_want in zip(got, want_caches):
        gsc, wsc = run_got["self"], run_want["self"]
        np.testing.assert_array_equal(np.asarray(gsc["pos"]),
                                      np.asarray(wsc["pos"]))
        valid = np.asarray(wsc["pos"]) >= 0            # (run, B, S)
        for name in ("k", "v", "ckv", "krope"):
            if name not in wsc:
                continue
            gv, wv = np.asarray(gsc[name]), np.asarray(wsc[name])
            vm = valid[:, :, None, :, None] if gv.ndim == 5 else \
                valid[:, :, :, None]
            np.testing.assert_allclose(np.where(vm, gv, 0.0),
                                       np.where(vm, wv, 0.0),
                                       atol=1e-5, rtol=1e-5)


def test_one_pass_forwards_context_exactly_once(setup):
    """Op-count assertion: with jit disabled every executed forward is
    counted — the fused path runs ONE prefill over prompt ⊕ draft and no
    teacher-forced forward; the two-pass path runs one of each."""
    cfg, params_a, params_b, prompt, mask = setup
    small = GenerateConfig(max_new_tokens=4)
    ids = list(range(B))
    cache1 = _seeded_cache(cfg, params_a, prompt, mask)
    cache2 = copy.deepcopy(cache1)

    with jax.disable_jit():
        M.reset_op_counts()
        rollout(params_b, cfg, small,
                SpecConfig(variant="spec", one_pass="on"),
                prompt, mask, ids, cache1, jax.random.PRNGKey(1), 1)
        assert M.OP_COUNTS["prefill"] == 1
        assert M.OP_COUNTS["forward"] == 0

        M.reset_op_counts()
        rollout(params_b, cfg, small,
                SpecConfig(variant="spec", one_pass="off"),
                prompt, mask, ids, cache2, jax.random.PRNGKey(1), 1)
        assert M.OP_COUNTS["prefill"] == 1     # continuation re-prefill
        assert M.OP_COUNTS["forward"] == 1     # scoring pass


def test_one_pass_auto_gating():
    """auto falls back to two-pass for recurrent trunks; 'on' raises."""
    from repro.core.spec_rollout import use_one_pass
    attn = ModelConfig(name="a", num_layers=2, d_model=64, num_heads=4,
                       num_kv_heads=2, d_ff=128, vocab_size=32)
    rec = ModelConfig(name="m", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=32,
                      block_kind="mamba", mamba_d_state=8)
    spec_auto = SpecConfig(variant="spec", one_pass="auto")
    assert use_one_pass(attn, spec_auto, {})
    assert not use_one_pass(rec, spec_auto, {})
    assert not use_one_pass(attn, SpecConfig(variant="full"), {})
    with pytest.raises(ValueError):
        use_one_pass(rec, SpecConfig(variant="spec", one_pass="on"), {})


def test_one_pass_with_encoder_extras(setup):
    """encoder_out flows through the fused verify and the resumed decode."""
    cfg = ModelConfig(name="ed", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=4, d_ff=128, vocab_size=32,
                      encoder_layers=2, encoder_frames=16,
                      cross_attention=True, pos_embed="learned",
                      max_seq_len=64)
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    bb, pp = 2, 6
    frames = jax.random.normal(jax.random.PRNGKey(1), (bb, 16, cfg.d_model))
    enc, epos = M.encode(params, cfg, frames)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (bb, pp), 3, 32)
    mask = jnp.ones((bb, pp), bool)
    gen = GenerateConfig(max_new_tokens=8)
    kw = dict(encoder_out=enc, encoder_positions=epos)
    cache = RolloutCache()
    spec = SpecConfig(variant="spec", one_pass="on")
    rollout(params, cfg, gen, spec, prompt, mask, [0, 1], cache,
            jax.random.PRNGKey(3), 0, **kw)
    rb = rollout(params, cfg, gen, spec, prompt, mask, [0, 1], cache,
                 jax.random.PRNGKey(4), 1, **kw)
    assert rb.metrics["one_pass"] == 1.0
    assert rb.metrics["accept_rate"] > 0.99      # same policy, l >= 1
    assert (rb.response_mask.sum(1) == rb.length).all()
