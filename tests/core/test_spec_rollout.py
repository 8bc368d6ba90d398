"""SPEC-RL orchestration: left_align, assemble, full pipeline invariants,
variant semantics (Table 2), cache freshness."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RolloutCache, SpecConfig, rollout
from repro.core.spec_rollout import assemble, left_align
from repro.engine.generate import GenerateConfig
from repro.models import model as M
from repro.models.config import ModelConfig


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(name="t", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=32)
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    B, P = 4, 8
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, P), 3, 32)
    mask = jnp.ones((B, P), bool)
    return cfg, params, prompt, mask


def test_left_align():
    tokens = jnp.array([[0, 0, 5, 6, 7, 0, 0],
                        [1, 2, 3, 0, 0, 0, 0]])
    mask = tokens > 0
    at, am = left_align(tokens, mask)
    np.testing.assert_array_equal(np.asarray(at[0]), [0, 0, 0, 0, 5, 6, 7])
    np.testing.assert_array_equal(np.asarray(at[1]), [0, 0, 0, 0, 1, 2, 3])
    np.testing.assert_array_equal(np.asarray(am.sum(1)), [3, 3])


def test_assemble():
    draft = jnp.array([[11, 12, 13, 14, 0, 0]], jnp.int32)
    prefix_lp = jnp.full((1, 6), -1.0)
    n = jnp.array([2], jnp.int32)
    cont = jnp.array([[21, 22, 0, 0, 0, 0]], jnp.int32)
    cont_lp = jnp.full((1, 6), -2.0)
    cont_len = jnp.array([2], jnp.int32)
    toks, lp, mask, total = assemble(draft, prefix_lp, n, cont, cont_lp,
                                     cont_len)
    np.testing.assert_array_equal(np.asarray(toks[0]), [11, 12, 21, 22, 0, 0])
    np.testing.assert_allclose(np.asarray(lp[0]), [-1, -1, -2, -2, 0, 0])
    assert int(total[0]) == 4
    np.testing.assert_array_equal(np.asarray(mask[0]),
                                  [True, True, True, True, False, False])


def test_identical_policy_full_acceptance(setup):
    """Same policy + l>=1 => every draft token verified (Eq. 3)."""
    cfg, params, prompt, mask = setup
    ids = list(range(prompt.shape[0]))
    gen = GenerateConfig(max_new_tokens=12)
    cache = RolloutCache()
    spec = SpecConfig(variant="spec", lenience=1.0)
    rollout(params, cfg, gen, spec, prompt, mask, ids, cache,
            jax.random.PRNGKey(0), 0)
    rb = rollout(params, cfg, gen, spec, prompt, mask, ids, cache,
                 jax.random.PRNGKey(1), 1)
    assert rb.metrics["accept_rate"] > 0.999
    assert rb.metrics["n_generated"] == 0 or \
        rb.metrics["n_generated"] < rb.metrics["n_reused"]


def test_cache_refreshed_after_step(setup):
    cfg, params, prompt, mask = setup
    ids = list(range(prompt.shape[0]))
    gen = GenerateConfig(max_new_tokens=8)
    cache = RolloutCache()
    spec = SpecConfig(variant="spec")
    rb0 = rollout(params, cfg, gen, spec, prompt, mask, ids, cache,
                  jax.random.PRNGKey(0), 0)
    for i, pid in enumerate(ids):
        e = cache.get(pid)
        L = int(rb0.length[i])
        np.testing.assert_array_equal(e.tokens, rb0.response[i, :L])
        assert e.step == 0
    rollout(params, cfg, gen, spec, prompt, mask, ids, cache,
            jax.random.PRNGKey(1), 1)
    assert all(cache.get(pid).step == 1 for pid in ids)
    # delayed reuse sees the step-0 rollout
    assert all(cache.get(pid, lag=2).step == 0 for pid in ids)


def test_variants_run_and_report(setup):
    cfg, params, prompt, mask = setup
    ids = list(range(prompt.shape[0]))
    gen = GenerateConfig(max_new_tokens=8)
    cache = RolloutCache()
    rollout(params, cfg, gen, SpecConfig(variant="spec"),
            prompt, mask, ids, cache, jax.random.PRNGKey(0), 0)
    rollout(params, cfg, gen, SpecConfig(variant="spec"),
            prompt, mask, ids, cache, jax.random.PRNGKey(1), 1)
    for variant in ("random", "delayed", "full", "off"):
        spec = SpecConfig(variant=variant)
        rb = rollout(params, cfg, gen, spec, prompt, mask, ids,
                     None if variant == "off" else cache,
                     jax.random.PRNGKey(2), 2)
        assert (rb.response_mask.sum(1) == rb.length).all()
        assert rb.response.shape == (4, 8)
        if variant == "full":
            assert rb.metrics["accept_rate"] == 1.0


def test_lenience_zero_equals_vanilla_token_counts(setup):
    """l -> 0 rejects at position 0: everything regenerated."""
    cfg, params, prompt, mask = setup
    ids = list(range(prompt.shape[0]))
    gen = GenerateConfig(max_new_tokens=8)
    cache = RolloutCache()
    spec0 = SpecConfig(variant="spec", lenience=1e-9)
    rollout(params, cfg, gen, spec0, prompt, mask, ids, cache,
            jax.random.PRNGKey(0), 0)
    rb = rollout(params, cfg, gen, spec0, prompt, mask, ids, cache,
                 jax.random.PRNGKey(1), 1)
    assert rb.metrics["n_reused"] == 0
    assert rb.metrics["n_generated"] > 0


def test_response_tokens_match_behaviour_source(setup):
    """Reused prefix tokens must equal the cached draft tokens."""
    cfg, params, prompt, mask = setup
    ids = list(range(prompt.shape[0]))
    gen = GenerateConfig(max_new_tokens=10)
    cache = RolloutCache()
    spec = SpecConfig(variant="spec", lenience=math.e ** 0.5)
    rb0 = rollout(params, cfg, gen, spec, prompt, mask, ids, cache,
                  jax.random.PRNGKey(0), 0)
    drafts = cache.batch_get(ids, 10)
    rb1 = rollout(params, cfg, gen, spec, prompt, mask, ids, cache,
                  jax.random.PRNGKey(1), 1)
    n_re = rb1.metrics["n_reused"]
    if n_re:
        # per-row: the first reused tokens agree with the old draft
        for i in range(len(ids)):
            L = min(int(rb1.length[i]), int(drafts["draft_len"][i]))
            agree = (rb1.response[i, :L] == drafts["draft_tokens"][i, :L])
            # everything before the first disagreement was the reused prefix
            assert agree[0] or rb1.metrics["verified_prefix_mean"] >= 0


def test_rollout_with_encoder_model_kwargs():
    """SPEC-RL plumbing for enc-dec archs: encoder_out flows through
    verification AND continuation (whisper-style decoder rollouts)."""
    cfg = ModelConfig(name="ed", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=4, d_ff=128, vocab_size=32,
                      encoder_layers=2, encoder_frames=16,
                      cross_attention=True, pos_embed="learned",
                      max_seq_len=64)
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    B, P = 2, 6
    frames = jax.random.normal(jax.random.PRNGKey(1), (B, 16, cfg.d_model))
    enc, epos = M.encode(params, cfg, frames)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (B, P), 3, 32)
    mask = jnp.ones((B, P), bool)
    gen = GenerateConfig(max_new_tokens=8)
    cache = RolloutCache()
    spec = SpecConfig(variant="spec")
    kw = dict(encoder_out=enc, encoder_positions=epos)
    rb0 = rollout(params, cfg, gen, spec, prompt, mask, [0, 1], cache,
                  jax.random.PRNGKey(3), 0, **kw)
    rb1 = rollout(params, cfg, gen, spec, prompt, mask, [0, 1], cache,
                  jax.random.PRNGKey(4), 1, **kw)
    assert rb1.metrics["accept_rate"] > 0.99     # same policy, l >= 1
    assert (rb1.response_mask.sum(1) == rb1.length).all()
