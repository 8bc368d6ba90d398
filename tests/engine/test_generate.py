"""Generation engine: behaviour-logprob consistency, eos stopping,
row budgets, initial_done skipping, left-padding invariance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine.generate import GenerateConfig, generate, positions_from_mask, score
from repro.models import model as M


@pytest.fixture(scope="module")
def setup(request):
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="t", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=32)
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(cfg, B=3, P=8, seed=1):
    prompt = jax.random.randint(jax.random.PRNGKey(seed), (B, P), 3,
                                cfg.vocab_size)
    mask = np.ones((B, P), bool)
    mask[0, :3] = False
    mask[2, :1] = False
    mask = jnp.asarray(mask)
    return jnp.where(mask, prompt, 0), mask


def test_logprobs_match_rescoring(setup):
    cfg, params = setup
    prompt, mask = _prompt(cfg)
    gen = GenerateConfig(max_new_tokens=10)
    out = generate(params, cfg, gen, prompt, mask, jax.random.PRNGKey(7))
    N = 10
    full = jnp.concatenate([prompt, out["tokens"]], axis=1)
    gmask = jnp.arange(N)[None, :] < out["length"][:, None]
    fmask = jnp.concatenate([mask, gmask], axis=1)
    sc = score(params, cfg, full, fmask)
    err = jnp.max(jnp.abs(jnp.where(gmask, sc["logprobs"][:, prompt.shape[1]:]
                                    - out["logprobs"], 0.0)))
    assert float(err) < 1e-4


def test_eos_stops_row(setup):
    cfg, params = setup
    prompt, mask = _prompt(cfg)
    gen = GenerateConfig(max_new_tokens=16, eos_id=2)
    out = generate(params, cfg, gen, prompt, mask, jax.random.PRNGKey(3))
    toks = np.asarray(out["tokens"])
    lens = np.asarray(out["length"])
    for i in range(toks.shape[0]):
        row = toks[i, :lens[i]]
        if 2 in row.tolist():
            assert row.tolist().index(2) == lens[i] - 1  # eos is last
        assert (toks[i, lens[i]:] == 0).all()            # pads after


def test_row_budget(setup):
    cfg, params = setup
    prompt, mask = _prompt(cfg)
    gen = GenerateConfig(max_new_tokens=16, eos_id=31)  # unlikely eos
    budget = jnp.array([4, 0, 9], jnp.int32)
    out = generate(params, cfg, gen, prompt, mask, jax.random.PRNGKey(5),
                   row_budget=budget)
    assert (np.asarray(out["length"]) <= np.asarray(budget)).all()
    assert int(out["length"][1]) == 0


def test_initial_done_skips_rows(setup):
    cfg, params = setup
    prompt, mask = _prompt(cfg)
    gen = GenerateConfig(max_new_tokens=8)
    done = jnp.array([True, False, True])
    out = generate(params, cfg, gen, prompt, mask, jax.random.PRNGKey(5),
                   initial_done=done)
    lens = np.asarray(out["length"])
    assert lens[0] == 0 and lens[2] == 0 and lens[1] > 0


@pytest.mark.parametrize("eos_id, budget, done", [
    (31, [4, 0, 9], None),                   # budgets end the rows
    (2, None, None),                         # eos or the token budget
    (31, None, [True, True, True]),          # nothing to decode
], ids=["budget", "eos", "all_done"])
def test_steps_is_the_decode_loops_trip_count(setup, eos_id, budget, done):
    """``steps`` counts while_loop iterations: the loop stops once every
    row is done, so it runs as many steps as the longest row generates."""
    cfg, params = setup
    prompt, mask = _prompt(cfg)
    gen = GenerateConfig(max_new_tokens=16, eos_id=eos_id)
    out = generate(params, cfg, gen, prompt, mask, jax.random.PRNGKey(5),
                   row_budget=None if budget is None else jnp.array(
                       budget, jnp.int32),
                   initial_done=None if done is None else jnp.array(done))
    assert int(out["steps"]) == int(np.asarray(out["length"]).max())


def test_left_padding_invariance(setup):
    """Extra left padding must not change greedy generation."""
    cfg, params = setup
    B, P = 1, 6
    prompt = jax.random.randint(jax.random.PRNGKey(9), (B, P), 3,
                                cfg.vocab_size)
    mask = jnp.ones((B, P), bool)
    gen = GenerateConfig(max_new_tokens=6, temperature=0.0)
    out1 = generate(params, cfg, gen, prompt, mask, jax.random.PRNGKey(0))
    pad = jnp.zeros((B, 3), jnp.int32)
    prompt2 = jnp.concatenate([pad, prompt], axis=1)
    mask2 = jnp.concatenate([jnp.zeros((B, 3), bool), mask], axis=1)
    out2 = generate(params, cfg, gen, prompt2, mask2, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(out1["tokens"]),
                                  np.asarray(out2["tokens"]))


def test_resume_from_cache_matches_generate(setup):
    """Decoding from an externally prefilled cache == prefill-inside-generate
    for the same key: the two engine entry points share one decode loop."""
    from repro.engine.generate import resume_from_cache
    cfg, params = setup
    prompt, mask = _prompt(cfg)
    B, P = prompt.shape
    N = 10
    gen = GenerateConfig(max_new_tokens=N)
    key = jax.random.PRNGKey(11)
    want = generate(params, cfg, gen, prompt, mask, key)

    caches = M.init_cache(cfg, B, P + N)
    logits, caches = M.prefill(params, cfg, prompt,
                               positions_from_mask(mask), caches)
    got = resume_from_cache(params, cfg, gen, caches, logits[:, -1],
                            mask.sum(axis=1).astype(jnp.int32), P, key)
    np.testing.assert_array_equal(np.asarray(got["tokens"]),
                                  np.asarray(want["tokens"]))
    np.testing.assert_array_equal(np.asarray(got["length"]),
                                  np.asarray(want["length"]))
    np.testing.assert_allclose(np.asarray(got["logprobs"]),
                               np.asarray(want["logprobs"]), atol=1e-6)


def test_score_first_token_and_pads_zero(setup):
    cfg, params = setup
    prompt, mask = _prompt(cfg)
    sc = score(params, cfg, prompt, mask)
    lp = np.asarray(sc["logprobs"])
    valid = np.asarray(sc["valid"])
    # first valid token of each row has no scored prefix
    for i in range(lp.shape[0]):
        first = int(np.argmax(np.asarray(mask)[i]))
        assert not valid[i, first]
        assert lp[i, first] == 0.0
    assert (lp[~valid] == 0.0).all()


# ------------------------------------------------- resume_from_cache edges


def _verify_resume(cfg, params, prompt, mask, draft_len_rows, log_lenience,
                   key, N=12, draft_eos_rows=None):
    """One-pass verify→compact→resume over crafted drafts; returns
    (n, cont, draft) for comparison against the two-pass reference."""
    from repro.core.spec_rollout import left_align
    from repro.core.verify import verify_and_prefill
    from repro.engine.generate import resume_from_cache
    B, P = prompt.shape
    draft = jax.random.randint(jax.random.PRNGKey(33), (B, N), 3,
                               cfg.vocab_size)
    if draft_eos_rows is not None:
        gen_eos = 2
        for i, dl in enumerate(draft_len_rows):
            if draft_eos_rows[i] and dl > 0:
                draft = draft.at[i, dl - 1].set(gen_eos)
    draft_len = jnp.asarray(draft_len_rows, jnp.int32)
    didx = jnp.arange(N)[None, :]
    # pessimistic behaviour log-probs: random drafts score ~ -log V under
    # the current policy, so -6 keeps the acceptance ratio near 1 and the
    # lenience knob controls rejection
    draft_lp = jnp.where(didx < draft_len[:, None], -6.0, 0.0)
    kv, kd = jax.random.split(key)
    ver = verify_and_prefill(params, cfg, prompt, mask, draft, draft_lp,
                             draft_len, kv, log_lenience)
    n = ver["n"]
    W = P + N
    p_len = mask.sum(axis=1).astype(jnp.int32)
    caches = M.realign_decode_cache(cfg, ver["caches"],
                                    (N - n).astype(jnp.int32), p_len + n, W)
    eos_at_n = jnp.take_along_axis(
        draft, jnp.maximum(n - 1, 0)[:, None], axis=1)[:, 0] == 2
    full_reuse = (n == draft_len) & (n > 0) & eos_at_n if draft_eos_rows \
        else jnp.zeros((B,), bool)
    gen = GenerateConfig(max_new_tokens=N)
    cont = resume_from_cache(params, cfg, gen, caches, ver["seed_logits"],
                             p_len + n, W, kd, initial_done=full_reuse,
                             row_budget=N - n)
    return n, cont, draft, draft_len, kd


def test_resume_zero_accepted_prefix(setup):
    """n = 0 everywhere (lenience -> 0 rejects all): resuming from the
    compacted verify cache == generating from the bare prompt."""
    from repro.core.spec_rollout import left_align
    cfg, params = setup
    prompt, mask = _prompt(cfg)
    N = 12
    key = jax.random.PRNGKey(21)
    n, cont, _, _, kd = _verify_resume(cfg, params, prompt, mask,
                                       [N, 7, 3], -1e9, key, N=N)
    assert (np.asarray(n) == 0).all()
    # reference: two-pass continuation over the aligned (prompt ⊕ nothing)
    W = prompt.shape[1] + N
    al_tok, al_mask = left_align(
        jnp.concatenate([prompt, jnp.zeros((3, N), jnp.int32)], axis=1),
        jnp.concatenate([mask, jnp.zeros((3, N), bool)], axis=1))
    want = generate(params, cfg, GenerateConfig(max_new_tokens=N), al_tok,
                    al_mask, kd, row_budget=jnp.full((3,), N, jnp.int32))
    np.testing.assert_array_equal(np.asarray(cont["tokens"]),
                                  np.asarray(want["tokens"]))
    np.testing.assert_array_equal(np.asarray(cont["length"]),
                                  np.asarray(want["length"]))


def test_resume_fully_accepted_draft_with_eos(setup):
    """Drafts fully accepted (lenience -> inf) and ending in EOS: the row is
    initially done, resumes zero tokens, and keeps its budget at 0."""
    cfg, params = setup
    prompt, mask = _prompt(cfg)
    N = 12
    n, cont, draft, draft_len, _ = _verify_resume(
        cfg, params, prompt, mask, [5, 8, N], 1e9, jax.random.PRNGKey(23),
        N=N, draft_eos_rows=[True, True, True])
    np.testing.assert_array_equal(np.asarray(n), np.asarray(draft_len))
    assert (np.asarray(cont["length"]) == 0).all()
    assert (np.asarray(cont["tokens"]) == 0).all()
    assert int(cont["n_generated"]) == 0


def test_resume_mixed_per_row_start_positions(setup):
    """Rows with different prompt lengths AND different accepted-prefix
    lengths resume from different cache depths; each row still matches the
    two-pass reference built from its own aligned context."""
    from repro.core.spec_rollout import left_align
    cfg, params = setup
    prompt, mask = _prompt(cfg)                  # mixed p_len already
    N = 12
    n, cont, draft, draft_len, kd = _verify_resume(
        cfg, params, prompt, mask, [0, 6, N], 0.3, jax.random.PRNGKey(25),
        N=N)
    n_np = np.asarray(n)
    assert len(set(n_np.tolist())) > 1           # genuinely mixed starts
    didx = jnp.arange(N)[None, :]
    prefix_mask = didx < n[:, None]
    al_tok, al_mask = left_align(
        jnp.concatenate([prompt, jnp.where(prefix_mask, draft, 0)], axis=1),
        jnp.concatenate([mask, prefix_mask], axis=1))
    want = generate(params, cfg, GenerateConfig(max_new_tokens=N), al_tok,
                    al_mask, kd, row_budget=N - n)
    np.testing.assert_array_equal(np.asarray(cont["tokens"]),
                                  np.asarray(want["tokens"]))
    np.testing.assert_array_equal(np.asarray(cont["length"]),
                                  np.asarray(want["length"]))
    np.testing.assert_allclose(np.asarray(cont["logprobs"]),
                               np.asarray(want["logprobs"]), atol=1e-5)
