"""Verification pass (Algorithm 1): acceptance math, statistical behaviour,
consistency between the model scoring pass and the acceptance rule."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.verify import verify_drafts
from repro.kernels.spec_verify.ref import spec_verify_ref
from repro.models import model as M
from repro.models.config import ModelConfig


def test_acceptance_probability_matches_eq3():
    """Monte-carlo: P(reject at 0) == 1 - min(1, l * q/p) for 1-token drafts."""
    trials = 30_000
    lp_curr = jnp.full((trials, 1), math.log(0.2))
    lp_prev = jnp.full((trials, 1), math.log(0.5))
    vl = jnp.ones((trials,), jnp.int32)
    for lenience in (1.0, math.e ** 0.5, 3.0):
        u = jax.random.uniform(jax.random.PRNGKey(int(lenience * 10)),
                               (trials, 1))
        n = np.asarray(spec_verify_ref(lp_curr, lp_prev, u, vl,
                                       math.log(lenience)))
        expect = min(1.0, lenience * 0.2 / 0.5)
        got = (n == 1).mean()
        assert abs(got - expect) < 0.01, (lenience, got, expect)


def test_verify_drafts_identical_policy(tiny_cfg, tiny_params):
    cfg, params = tiny_cfg, tiny_params
    B, P, N = 3, 6, 10
    prompt = jax.random.randint(jax.random.PRNGKey(0), (B, P), 3,
                                cfg.vocab_size)
    pmask = jnp.ones((B, P), bool)
    # draft = greedy continuation; p_prev = exact scoring by the same model
    from repro.engine.generate import GenerateConfig, generate, score
    gen = GenerateConfig(max_new_tokens=N)
    out = generate(params, cfg, gen, prompt, pmask, jax.random.PRNGKey(1))
    res = verify_drafts(params, cfg, prompt, pmask, out["tokens"],
                        out["logprobs"], out["length"], jax.random.PRNGKey(2),
                        0.0)
    # p_curr == p_prev exactly (same model) => full acceptance at l=1
    np.testing.assert_array_equal(np.asarray(res["n"]),
                                  np.asarray(out["length"]))
    assert float(res["accept_rate"]) == 1.0


def test_verify_drafts_prefix_consistency(tiny_cfg, tiny_params):
    """lp_curr from the packed verify == scoring the same tokens directly."""
    cfg, params = tiny_cfg, tiny_params
    B, P, N = 2, 5, 6
    prompt = jax.random.randint(jax.random.PRNGKey(3), (B, P), 3,
                                cfg.vocab_size)
    pmask = jnp.ones((B, P), bool)
    draft = jax.random.randint(jax.random.PRNGKey(4), (B, N), 3,
                               cfg.vocab_size)
    dlen = jnp.array([6, 4], jnp.int32)
    dlp = jnp.full((B, N), -1.0)
    res = verify_drafts(params, cfg, prompt, pmask, draft, dlp, dlen,
                        jax.random.PRNGKey(5), 0.0)
    from repro.engine.generate import score
    didx = jnp.arange(N)[None, :]
    dmask = didx < dlen[:, None]
    full = jnp.concatenate([prompt, jnp.where(dmask, draft, 0)], axis=1)
    fmask = jnp.concatenate([pmask, dmask], axis=1)
    sc = score(params, cfg, full, fmask)
    np.testing.assert_allclose(np.asarray(res["lp_curr"]),
                               np.asarray(sc["logprobs"][:, P:]), atol=1e-5)


def test_perturbed_policy_reduces_acceptance(tiny_cfg, tiny_params):
    """A perturbed current policy must reject more than an identical one."""
    cfg, params = tiny_cfg, tiny_params
    B, P, N = 8, 6, 12
    prompt = jax.random.randint(jax.random.PRNGKey(6), (B, P), 3,
                                cfg.vocab_size)
    pmask = jnp.ones((B, P), bool)
    from repro.engine.generate import GenerateConfig, generate
    gen = GenerateConfig(max_new_tokens=N)
    out = generate(params, cfg, gen, prompt, pmask, jax.random.PRNGKey(7))

    perturbed = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.PRNGKey(8), x.shape)
        .astype(x.dtype), params)
    same = verify_drafts(params, cfg, prompt, pmask, out["tokens"],
                         out["logprobs"], out["length"],
                         jax.random.PRNGKey(9), 0.0)
    diff = verify_drafts(perturbed, cfg, prompt, pmask, out["tokens"],
                         out["logprobs"], out["length"],
                         jax.random.PRNGKey(9), 0.0)
    assert float(diff["n"].sum()) < float(same["n"].sum())


def test_lenience_recovers_acceptance(tiny_cfg, tiny_params):
    """Higher lenience recovers longer prefixes on a perturbed policy
    (Fig. 4c mechanism), with shared verification randomness."""
    cfg, params = tiny_cfg, tiny_params
    B, P, N = 8, 6, 12
    prompt = jax.random.randint(jax.random.PRNGKey(10), (B, P), 3,
                                cfg.vocab_size)
    pmask = jnp.ones((B, P), bool)
    from repro.engine.generate import GenerateConfig, generate
    gen = GenerateConfig(max_new_tokens=N)
    out = generate(params, cfg, gen, prompt, pmask, jax.random.PRNGKey(11))
    perturbed = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.PRNGKey(12),
                                               x.shape).astype(x.dtype),
        params)
    ns = []
    for logl in (0.0, 0.5, 2.0):
        r = verify_drafts(perturbed, cfg, prompt, pmask, out["tokens"],
                          out["logprobs"], out["length"],
                          jax.random.PRNGKey(13), logl)
        ns.append(int(r["n"].sum()))
    assert ns[0] <= ns[1] <= ns[2]
