"""Beyond-paper features: adaptive lenience, trainer checkpoint/resume
with a warm rollout cache."""
import jax
import numpy as np
import pytest

from repro.core import SpecConfig
from repro.core.lenience import (AdaptiveLenience, FixedLenience,
                                 LinearWarmupLenience, make_schedule)
from repro.data.dataset import PromptDataset
from repro.data.tokenizer import VOCAB_SIZE
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamWConfig
from repro.rewards.mathgen import MathTaskConfig, generate_problems
from repro.rl.trainer import RLConfig, Trainer


# ------------------------------------------------------------------ lenience


def test_fixed_and_warmup_schedules():
    f = FixedLenience(2.0)
    assert f(0) == f(100) == 2.0
    w = LinearWarmupLenience(target=4.0, warmup_steps=10)
    assert w(0) == pytest.approx(1.0)
    assert w(10) == pytest.approx(4.0)
    assert 1.0 < w(5) < 4.0


def test_adaptive_lenience_controller():
    a = AdaptiveLenience(init=1.0, budget=0.05, gain=1.0, lo=1.0,
                         hi=np.e ** 2)
    # under budget: lenience grows
    for _ in range(3):
        a.update(0.0)
    assert a(0) > 1.0
    # way over budget: shrinks back to the floor
    for _ in range(20):
        a.update(1.0)
    assert a(0) == pytest.approx(1.0)
    assert make_schedule("adaptive", budget=0.1)(0) >= 1.0


def test_trainer_with_adaptive_lenience():
    cfg = ModelConfig(name="t", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=VOCAB_SIZE,
                      max_seq_len=128)
    ds = PromptDataset(generate_problems(MathTaskConfig(num_problems=8,
                                                        max_operand=5)),
                       max_prompt_len=10)
    rl = RLConfig(algo="grpo", group_size=2, prompts_per_batch=4,
                  max_new_tokens=6, optim=AdamWConfig(lr=2e-3))
    tr = Trainer(cfg, rl, SpecConfig(variant="spec"), ds,
                 jax.random.PRNGKey(0),
                 lenience_schedule=AdaptiveLenience(init=1.0, budget=0.05,
                                                    gain=5.0))
    ls = [tr.train_step()["lenience"] for _ in range(3)]
    assert ls[-1] != ls[0]          # the controller moved lenience


# ------------------------------------------------------------------ resume


def test_trainer_checkpoint_resume(tmp_path):
    """Params + opt + rollout cache roundtrip; resumed trainer keeps reusing
    (no second cold start)."""
    from repro.checkpoint.io import (load_pytree, load_rollout_cache,
                                     save_pytree, save_rollout_cache)
    cfg = ModelConfig(name="t", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=VOCAB_SIZE,
                      max_seq_len=128)
    ds = PromptDataset(generate_problems(MathTaskConfig(num_problems=6,
                                                        max_operand=5)),
                       max_prompt_len=10)
    rl = RLConfig(algo="grpo", group_size=2, prompts_per_batch=3,
                  max_new_tokens=6, optim=AdamWConfig(lr=1e-3))
    spec = SpecConfig(variant="spec")
    tr = Trainer(cfg, rl, spec, ds, jax.random.PRNGKey(0))
    for _ in range(2):
        tr.train_step()
    path = str(tmp_path / "ck")
    save_pytree(path, {"params": tr.params, "opt": tr.opt_state},
                {"step": tr.step_idx})
    save_rollout_cache(path, tr.cache)

    tr2 = Trainer(cfg, rl, spec, ds, jax.random.PRNGKey(0))
    state, meta = load_pytree(path)
    tr2.params = state["params"]
    tr2.opt_state = state["opt"]
    tr2.step_idx = meta["step"]
    tr2.cache = load_rollout_cache(path)
    assert len(tr2.cache) == len(tr.cache)
    m = tr2.train_step()
    # warm cache => reuse on the very first resumed step
    assert m.get("n_reused", 0) > 0 or m.get("draft_coverage", 0) > 0
