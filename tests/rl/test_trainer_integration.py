"""Trainer integration: all three algorithms x SPEC-RL run end-to-end;
GRPO improves reward on a trivial task from random init."""
import math

import jax
import numpy as np
import pytest

from repro.core import SpecConfig
from repro.data.dataset import PromptDataset
from repro.data.tokenizer import VOCAB_SIZE
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamWConfig
from repro.rewards.mathgen import MathTaskConfig, generate_problems
from repro.rl.trainer import RLConfig, Trainer


def _make_trainer(algo, variant="spec", steps_cfg=None, seed=0,
                  dtype="float32"):
    cfg = ModelConfig(name="tiny", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=VOCAB_SIZE,
                      max_seq_len=128, dtype=dtype, param_dtype=dtype)
    problems = generate_problems(MathTaskConfig(num_problems=8, max_operand=4))
    ds = PromptDataset(problems, max_prompt_len=10)
    rl = RLConfig(algo=algo, group_size=2, prompts_per_batch=4,
                  max_new_tokens=6, optim=AdamWConfig(lr=1e-3),
                  max_resample_rounds=1, **(steps_cfg or {}))
    spec = SpecConfig(variant=variant, lenience=math.e ** 0.5)
    return Trainer(cfg, rl, spec, ds, jax.random.PRNGKey(seed))


@pytest.mark.parametrize("algo", ["grpo", "ppo", "dapo"])
def test_algo_runs_with_spec_rl(algo):
    tr = _make_trainer(algo)
    for _ in range(3):
        m = tr.train_step()
    assert np.isfinite(m["loss"])
    assert m["total_generated_tokens"] > 0
    if algo == "ppo":
        assert "critic_loss" in m
    if algo == "dapo":
        assert m["gen_steps"] >= 3   # dynamic sampling may add rounds


def test_spec_rl_reduces_generated_tokens():
    """After the cold-start epoch, SPEC-RL reuses: fewer generated tokens
    than the vanilla variant at the same steps (paper Table 1 mechanism)."""
    tr_spec = _make_trainer("grpo", variant="spec", seed=1)
    tr_off = _make_trainer("grpo", variant="off", seed=1)
    for _ in range(4):
        tr_spec.train_step()
        tr_off.train_step()
    assert tr_spec.total_generated_tokens < tr_off.total_generated_tokens


def test_update_step_compiles_once_with_bf16_params():
    """AdamW moments start in float32, the dtype the update returns them
    in, so the update step keeps one signature from its first step on."""
    from repro.obs.alerts import jit_cache_size
    from repro.rl.trainer import _update_actor
    tr = _make_trainer("grpo", dtype="bfloat16")
    before = jit_cache_size(_update_actor)
    for _ in range(2):
        assert np.isfinite(tr.train_step()["loss"])
    assert jit_cache_size(_update_actor) - before == 1


def test_kl_ref_tracked_for_grpo():
    tr = _make_trainer("grpo")
    m = tr.train_step()
    assert "kl_ref" in m


@pytest.mark.slow
def test_grpo_learns_single_digit_addition():
    """Reward improves on an easy task within a modest budget."""
    cfg = ModelConfig(name="learn", num_layers=2, d_model=96, num_heads=4,
                      num_kv_heads=2, d_ff=192, vocab_size=VOCAB_SIZE,
                      max_seq_len=64)
    problems = generate_problems(MathTaskConfig(
        num_problems=6, min_operand=1, max_operand=3, ops="+"))
    ds = PromptDataset(problems, max_prompt_len=8)
    rl = RLConfig(algo="grpo", group_size=8, prompts_per_batch=6,
                  max_new_tokens=4, optim=AdamWConfig(lr=4e-3),
                  temperature=1.0)
    tr = Trainer(cfg, rl, SpecConfig(variant="spec"), ds,
                 jax.random.PRNGKey(0))
    rewards = [tr.train_step()["reward_mean"] for _ in range(30)]
    early = np.mean(rewards[:5])
    late = np.mean(rewards[-5:])
    assert late > early + 0.1, f"no learning: early={early}, late={late}"
