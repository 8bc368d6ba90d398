"""Slot-scheduler correctness: for per-request PRNG keys, tokens produced
through the continuous-batching engine are identical to fixed-batch
``generate`` / one-pass ``rollout`` — including speculative-prefix admission
and the cache_slot_write admission path (ISSUE 2 acceptance criterion)."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RolloutCache, SpecConfig, rollout
from repro.engine.generate import GenerateConfig, generate, positions_from_mask
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.serving import Request, SlotEngine

B, P, N = 6, 8, 12


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(name="t", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=32)
    params_a = M.init_lm(jax.random.PRNGKey(0), cfg)
    params_b = M.init_lm(jax.random.PRNGKey(42), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, P), 3, 32)
    mask = np.ones((B, P), bool)
    mask[0, :3] = False                    # mixed prompt lengths
    mask[3, :2] = False
    prompt = jnp.where(jnp.asarray(mask), prompt, 0)
    return cfg, params_a, params_b, prompt, jnp.asarray(mask)


def _row_keys(seed, n=B):
    return jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(seed), i)
                    )(jnp.arange(n))


def test_slot_engine_matches_fixed_batch_generate(setup):
    """2 slots drain 6 requests with long-tailed budgets; every request's
    tokens/logprobs/length equal the fixed-batch generate row."""
    cfg, params, _, prompt, mask = setup
    gen = GenerateConfig(max_new_tokens=N)
    keys = _row_keys(7)
    budget = jnp.array([N, 3, 7, N, 1, 5], jnp.int32)
    ref = generate(params, cfg, gen, prompt, mask, keys, row_budget=budget)

    eng = SlotEngine(params, cfg, gen, num_slots=2, prompt_width=P,
                     chunk_steps=4)
    kn, pn, mn = np.asarray(keys), np.asarray(prompt), np.asarray(mask)
    for i in range(B):
        pl = int(mn[i].sum())
        eng.submit(Request(request_id=i, prompt=pn[i, P - pl:], key=kn[i],
                           max_new_tokens=int(budget[i])))
    resps = eng.run()
    for i in range(B):
        L = int(ref["length"][i])
        assert resps[i].length == L
        np.testing.assert_array_equal(resps[i].tokens,
                                      np.asarray(ref["tokens"])[i, :L])
        np.testing.assert_allclose(resps[i].logprobs,
                                   np.asarray(ref["logprobs"])[i, :L],
                                   atol=1e-5, rtol=1e-5)
    st = eng.stats()
    assert st["completed"] == B and st["pending"] == 0
    assert st["generated_tokens"] == float(np.asarray(ref["length"]).sum())


def _seeded_cache(cfg, params, prompt, mask):
    cache = RolloutCache()
    spec = SpecConfig(variant="spec", one_pass="off")
    gen = GenerateConfig(max_new_tokens=N)
    rollout(params, cfg, gen, spec, prompt, mask, list(range(B)), cache,
            jax.random.PRNGKey(0), 0)
    return cache


@pytest.mark.parametrize("variant", ["spec", "delayed"])
def test_backfill_slots_matches_fixed_batch_rollout(setup, variant):
    """rollout(spec.backfill='slots') == fixed-batch one-pass rollout under
    the same per-request keys: responses, lengths, behaviour log-probs,
    reuse metrics and the refreshed cache all agree."""
    cfg, params_a, params_b, prompt, mask = setup
    gen = GenerateConfig(max_new_tokens=N)
    ids = list(range(B))
    cache1 = _seeded_cache(cfg, params_a, prompt, mask)
    if variant == "delayed":
        rollout(params_a, cfg, gen,
                SpecConfig(variant="spec", one_pass="off"),
                prompt, mask, ids, cache1, jax.random.PRNGKey(5), 1)
    cache2 = copy.deepcopy(cache1)

    keys = _row_keys(9)
    fixed = rollout(params_b, cfg, gen,
                    SpecConfig(variant=variant, one_pass="on"),
                    prompt, mask, ids, cache1, keys, 2)
    slots = rollout(params_b, cfg, gen,
                    SpecConfig(variant=variant, one_pass="on",
                               backfill="slots", backfill_slots=2),
                    prompt, mask, ids, cache2, keys, 2)

    np.testing.assert_array_equal(slots.response, fixed.response)
    np.testing.assert_array_equal(slots.length, fixed.length)
    np.testing.assert_array_equal(slots.response_mask, fixed.response_mask)
    np.testing.assert_allclose(slots.behaviour_logprobs,
                               fixed.behaviour_logprobs, atol=1e-5, rtol=1e-5)
    assert slots.metrics["n_reused"] == fixed.metrics["n_reused"]
    assert slots.metrics["n_generated"] == fixed.metrics["n_generated"]
    assert slots.metrics["n_reused"] > 0          # non-trivial comparison
    assert slots.metrics["backfill_slots"] == 2.0
    for i in ids:                                 # immediate cache refresh
        np.testing.assert_array_equal(cache1.get(i).tokens,
                                      cache2.get(i).tokens)


def test_backfill_slots_vanilla_cold_start(setup):
    """Cold start (no drafts): slots mode matches the vanilla rollout path."""
    cfg, params, _, prompt, mask = setup
    gen = GenerateConfig(max_new_tokens=N)
    ids = list(range(B))
    keys = _row_keys(11)
    fixed = rollout(params, cfg, gen, SpecConfig(variant="spec"),
                    prompt, mask, ids, RolloutCache(), keys, 0)
    slots = rollout(params, cfg, gen,
                    SpecConfig(variant="spec", backfill="slots",
                               backfill_slots=3),
                    prompt, mask, ids, RolloutCache(), keys, 0)
    np.testing.assert_array_equal(slots.response, fixed.response)
    np.testing.assert_array_equal(slots.length, fixed.length)
    assert slots.metrics["one_pass"] == 0.0


def test_spec_prefix_admission_with_interpret_kernels(setup,
                                                      interpret_kernels):
    """The Pallas admission kernels (interpret mode) on the real slot path:
    cache_slot_write + cache_gather produce the same responses as ref."""
    cfg, params_a, params_b, prompt, mask = setup
    gen = GenerateConfig(max_new_tokens=N)
    ids = list(range(B))
    cache1 = _seeded_cache(cfg, params_a, prompt, mask)
    cache2 = copy.deepcopy(cache1)
    keys = _row_keys(13)
    base = SpecConfig(variant="spec", one_pass="on",
                      backfill="slots", backfill_slots=2)
    ref = rollout(params_b, cfg, gen, base, prompt, mask, ids, cache1, keys,
                  1)
    with interpret_kernels("cache_gather", "cache_slot_write"):
        ker = rollout(params_b, cfg, gen, base, prompt, mask, ids, cache2,
                      keys, 1)
    np.testing.assert_array_equal(ker.response, ref.response)
    np.testing.assert_array_equal(ker.length, ref.length)


def test_write_cache_slots_exact(setup):
    """write_cache_slots: admitted rows equal the source caches leaf-for-leaf,
    untouched slots bit-identical to the old cache."""
    cfg, params, _, prompt, mask = setup
    caches_a = M.init_cache(cfg, 4, P + 4)
    logits, caches_b = M.prefill(params, cfg, prompt[:2],
                                 positions_from_mask(mask[:2]),
                                 M.init_cache(cfg, 2, P + 4))
    slots = jnp.array([2, 0], jnp.int32)
    out = M.write_cache_slots(cfg, caches_a, caches_b, slots)
    for run_out, run_a, run_b in zip(out, caches_a, caches_b):
        for name in run_out["self"]:
            o = np.asarray(run_out["self"][name])
            a = np.asarray(run_a["self"][name])
            b = np.asarray(run_b["self"][name])
            np.testing.assert_array_equal(o[:, 2], b[:, 0])
            np.testing.assert_array_equal(o[:, 0], b[:, 1])
            np.testing.assert_array_equal(o[:, 1], a[:, 1])
            np.testing.assert_array_equal(o[:, 3], a[:, 3])


def test_arrival_stream_and_states(setup):
    """Requests arriving mid-run are served; lifecycle reaches DONE with a
    finish reason; idle fast-forward does not deadlock."""
    from repro.serving.request import DONE
    cfg, params, _, prompt, mask = setup
    gen = GenerateConfig(max_new_tokens=N, eos_id=31)   # rare eos
    eng = SlotEngine(params, cfg, gen, num_slots=2, prompt_width=P,
                     chunk_steps=4)
    kn, pn, mn = np.asarray(_row_keys(15)), np.asarray(prompt), np.asarray(mask)
    reqs = []
    for i in range(4):
        pl = int(mn[i].sum())
        reqs.append(Request(request_id=i, prompt=pn[i, P - pl:], key=kn[i],
                            max_new_tokens=4 if i % 2 else N))
    # two up front, one mid-run, one far beyond the natural drain point
    eng.submit(reqs[0])
    eng.submit(reqs[1])
    resps = eng.run(arrivals=[(4, reqs[2]), (10 ** 4, reqs[3])])
    assert sorted(resps) == [0, 1, 2, 3]
    assert all(r.state == DONE for r in reqs)
    assert {resps[i].finish_reason for i in range(4)} <= {"eos", "budget"}
    assert resps[3].length > 0
