"""A DeepSeek-V3 decoder (Moonlight-16B-A3B's block: latent attention, a
dense first layer, sigmoid-routed experts beside shared ones, a held share
of the experts) against the benchmark's plain float32 reference
(benchmarks/onchip/configs/deepseek_v3_moe.py), at a small size on seeded
random weights.

The program runs in float32 with matmuls at ``highest``, as the reference
does, so the two differ only by the order of float32 operations (the
absorbed latent decode among them): 1e-4 on logits and log-probs is two
orders above the 2e-6 they read and three below what a dropped term (one
expert, the shared experts, the rope part of a score) moves."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SpecConfig
from repro.core.cache import RolloutCache
from repro.core.spec_rollout import rollout
from repro.engine.generate import GenerateConfig
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.models.moe import _router, apply_moe, make_moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
TOL = 1e-4
E, K, HELD0, NHELD = 8, 3, 2, 4


def _reference():
    path = os.path.join(ROOT, "benchmarks", "onchip", "configs",
                        "deepseek_v3_moe.py")
    spec = importlib.util.spec_from_file_location("ref_deepseek_v3", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
CONFIG = {"first_k_dense_replace": 1, "hidden_size": 64,
          "intermediate_size": 128, "kv_lora_rank": 32,
          "moe_intermediate_size": 32, "moe_layer_freq": 1, "n_group": 1,
          "n_routed_experts": NHELD, "n_router_experts": E,
          "first_held_expert": HELD0, "n_shared_experts": 2,
          "norm_topk_prob": True, "num_attention_heads": 4,
          "num_experts_per_tok": K, "num_hidden_layers": 3,
          "q_lora_rank": None, "qk_nope_head_dim": 16,
          "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5, "rope_theta": 50000,
          "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
          "tie_word_embeddings": False, "topk_group": 1,
          "topk_method": "noaux_tc", "v_head_dim": 16, "vocab_size": 96}
SIZES = REF.sizes_of(CONFIG)


def _cfg(**kw):
    return ModelConfig(**{**dict(
        name="moon", num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
        d_ff=128, vocab_size=96, norm_eps=1e-5, attention_kind="mla",
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=50000.0, num_experts=E,
        num_experts_per_tok=K, num_shared_experts=2, moe_d_ff=32,
        first_dense_layers=1, router_score="sigmoid", routed_scaling=2.446,
        held_expert_start=HELD0, num_held_experts=NHELD), **kw})


def _program_params(w):
    """The reference's weights as the program's tree, in float32 (the
    benchmark's adapter, deepseek_v3_moe_program.py, does the layout)."""
    path = os.path.join(ROOT, "benchmarks", "onchip", "configs",
                        "deepseek_v3_moe_program.py")
    spec = importlib.util.spec_from_file_location("prog_deepseek_v3", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        mod.to_program_params(w))


@pytest.fixture(scope="module")
def weights():
    return REF.init_weights(jax.random.PRNGKey(0), SIZES)


@pytest.mark.parametrize("impl", ["naive", "interpret"])
def test_prefill_then_decode_matches_reference_logits(weights, impl):
    """Prefill of a left-padded prompt, then decode steps through the
    latent cache (jnp absorbed form, and the latent kernel reading the
    stack in place), against the reference's full forward."""
    cfg = _cfg(decode_impl=impl)
    params = _program_params(weights)
    B, P, N = 2, 8, 5
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, P + N), 3, 96)
    pad = jnp.array([0, 3])
    mask = jnp.arange(P)[None] >= pad[:, None]
    pos = jnp.where(mask, jnp.cumsum(mask, 1) - 1, -1)
    prefill = jax.jit(M.prefill, static_argnums=(1,))
    step = jax.jit(M.decode_step, static_argnums=(1,))
    with jax.default_matmul_precision("highest"):
        caches = M.init_cache(cfg, B, P + N)
        logits, caches = prefill(params, cfg, toks[:, :P], pos, caches)
        got = [logits[:, -1]]
        for t in range(N - 1):
            lg, caches = step(params, cfg, toks[:, P + t:P + t + 1],
                              (P - pad + t)[:, None], caches, P + t)
            got.append(lg[:, 0])
        want = REF.forward_logits(weights, SIZES, toks[:1, :P + N - 1],
                                  "reference")
        want2 = REF.forward_logits(weights, SIZES, toks[1:, 3:P + N - 1],
                                   "reference")
    got = np.stack([np.asarray(g) for g in got], 1)      # (B, N, V)
    np.testing.assert_allclose(got[0], np.asarray(want[0, P - 1:]),
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(got[1], np.asarray(want2[0, P - 4:]),
                               atol=TOL, rtol=TOL)


def test_one_pass_reuse_matches_reference_and_two_pass(weights):
    """verify -> compact -> resume serves the same tokens as the two-pass
    path, and every served token's log-prob is the reference's."""
    cfg = _cfg()
    params = _program_params(weights)
    B, P, N = 4, 6, 12
    prompts = jax.random.randint(jax.random.PRNGKey(2), (B, P), 3, 96)
    mask = jnp.ones((B, P), bool)
    drafts = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (B, N),
                                           3, 96))
    gen = GenerateConfig(max_new_tokens=N)
    outs = {}
    for one_pass in ("on", "off"):
        cache = RolloutCache(history=2)
        # drafts far below the policy's log-probs up to a row's rejection
        # point, far above after it: rejected exactly there
        lp = np.full((B, N), -1e4, np.float32)
        for b, n in enumerate((3, 7, 0, 10)):
            lp[b, n:] = 1e4
        cache.batch_put(list(range(B)), drafts, lp, np.full(B, N), 0,
                        eos_id=2)
        spec = SpecConfig(one_pass=one_pass)
        with jax.default_matmul_precision("highest"):
            outs[one_pass] = rollout(params, cfg, gen, spec, prompts, mask,
                                     list(range(B)), cache,
                                     jax.random.PRNGKey(4), 1)
    on, off = outs["on"], outs["off"]
    assert on.metrics["one_pass"] == 1.0 and off.metrics["one_pass"] == 0.0
    np.testing.assert_array_equal(on.response, off.response)
    np.testing.assert_allclose(on.behaviour_logprobs, off.behaviour_logprobs,
                               atol=TOL, rtol=TOL)
    full = np.concatenate([np.asarray(prompts), on.response], 1)
    lens = P + on.length
    want = np.asarray(REF.token_logprobs(weights, SIZES, jnp.asarray(full),
                                         jnp.asarray(lens), "reference"))
    for b in range(B):
        L = int(on.length[b])
        np.testing.assert_allclose(on.behaviour_logprobs[b, :L],
                                   want[b, P:P + L], atol=TOL, rtol=TOL)


def test_router_is_the_published_sigmoid_noaux_tc():
    """Chosen on score + correction bias, weighted by the unbiased scores
    normalised over the chosen and scaled (DeepSeek-V3, noaux_tc, one
    group), written out in numpy."""
    cfg = _cfg()
    p = make_moe(jax.random.PRNGKey(5), cfg, jnp.float32)
    p["e_score_correction_bias"] = jax.random.normal(jax.random.PRNGKey(6),
                                                     (E,)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(7), (40, 64))
    w, idx, _ = _router(p, cfg, x)
    logits = np.asarray(x, np.float64) @ np.asarray(p["router"]["kernel"],
                                                    np.float64)
    s = 1.0 / (1.0 + np.exp(-logits))
    choice = s + np.asarray(p["e_score_correction_bias"], np.float64)
    want_idx = np.argsort(-choice, axis=1)[:, :K]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), 1),
                                  np.sort(want_idx, 1))
    ws = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(np.asarray(w), ws / ws.sum(1, keepdims=True)
                               * 2.446, rtol=1e-5)
    # the bias moves the choice, not the weights: unbiased top-k differs
    assert (np.sort(np.argsort(-s, 1)[:, :K], 1)
            != np.sort(want_idx, 1)).any()


def test_held_shares_sum_to_the_uncut_layer():
    """Four chips holding 2 experts each: their routed parts, plus the
    shared experts once, add up to the layer that holds all 8."""
    full = _cfg(held_expert_start=0, num_held_experts=0)
    p = make_moe(jax.random.PRNGKey(8), full, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 6, 64))
    want, aux = apply_moe(p, full, x)
    assert int(aux["moe_held_tokens"]) == 2 * 6 * K
    shared = apply_moe(dict(p, w_down=jnp.zeros_like(p["w_down"])), full,
                       x)[0]
    total = shared
    for c in range(4):
        share = _cfg(held_expert_start=2 * c, num_held_experts=2)
        pc = dict(p, **{n: p[n][2 * c:2 * c + 2]
                        for n in ("w_gate", "w_up", "w_down")})
        total = total + apply_moe(pc, share, x)[0] - shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_held_layer_drops_no_token_under_imbalance():
    """Every token routes to held expert 3 first (its correction bias is
    far above the rest): each token still gets that expert's full output,
    as a per-token loop computes it."""
    cfg = _cfg(num_shared_experts=0)
    p = make_moe(jax.random.PRNGKey(10), cfg, jnp.float32)
    p["e_score_correction_bias"] = jnp.zeros((E,)).at[3].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(11), (3, 16, 64))
    got, aux = apply_moe(p, cfg, x)
    w, idx, _ = _router(p, cfg, x.reshape(-1, 64))
    assert (np.asarray(idx)[:, 0] == 3).all()
    xf = np.asarray(x.reshape(-1, 64), np.float64)
    want = np.zeros_like(xf)
    for n in range(xf.shape[0]):
        for j in range(K):
            e = int(idx[n, j]) - HELD0
            if 0 <= e < NHELD:
                g = xf[n] @ np.asarray(p["w_gate"][e], np.float64)
                u = xf[n] @ np.asarray(p["w_up"][e], np.float64)
                h = g / (1 + np.exp(-g)) * u
                want[n] += float(w[n, j]) * (h @ np.asarray(p["w_down"][e],
                                                            np.float64))
    np.testing.assert_allclose(np.asarray(got).reshape(-1, 64), want,
                               atol=1e-4, rtol=1e-4)
    held = (np.asarray(idx) >= HELD0) & (np.asarray(idx) < HELD0 + NHELD)
    assert int(aux["moe_held_tokens"]) == held.sum() >= 48



@pytest.mark.parametrize("held", [0, NHELD])
def test_held_count_reaches_the_collector_times(weights, held):
    """The decode loop's count of routed assignments on held experts comes
    back in the rollout's metrics, into ``Collector.collect``'s times.
    Holding every expert, it is exactly k per decoded token and MoE layer;
    holding 4 of 8, fewer."""
    from repro.data.dataset import PromptBatch
    from repro.rl.trainer import Collector, RLConfig
    cfg = _cfg(held_expert_start=HELD0 if held else 0, num_held_experts=held,
               dtype="float32", param_dtype="float32")
    params = M.init_lm(jax.random.PRNGKey(12), cfg)
    rl = RLConfig(group_size=2, prompts_per_batch=2, max_new_tokens=6)
    col = Collector(cfg, rl, SpecConfig(), None, jax.random.PRNGKey(13))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(14), (4, 5), 3,
                                         96), np.int32)
    batch = PromptBatch(tokens=toks, mask=np.ones((4, 5), bool),
                        cache_keys=[0, 1, 2, 3], answers=[0] * 4,
                        problem_ids=[0, 1, 2, 3], epoch=0)
    _, _, _, times = col.collect(params, batch, 0)
    moe_layers = cfg.num_layers - cfg.first_dense_layers
    full = K * moe_layers * times["n_generated"]
    if held:
        assert 0 <= times["moe_held_tokens"] < full
    else:
        assert times["moe_held_tokens"] == full
