"""Disaggregated async rollout ↔ train (DESIGN.md §12).

The §12 acceptance contract, end to end: K=0 under the deterministic
step-interleaved scheduler is token- and loss-identical to the synchronous
trainer; staleness ≤ K is IS-corrected; staleness > K re-verifies through
the SPEC-RL draft path; persistent weight-sync failure walks the mode
ladder down to synchronous; a producer kill + a failed sync (the seeded
chaos pair) completes without crashing; and the whole pair kill-and-resumes
byte-identically through checkpoint/io.
"""
import math

import jax
import numpy as np
import pytest

import repro.obs as obs
from repro.core import SpecConfig
from repro.core.backoff import BackoffConfig
from repro.data.dataset import PromptDataset
from repro.data.tokenizer import VOCAB_SIZE
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamWConfig
from repro.rewards.mathgen import MathTaskConfig, generate_problems
from repro.rl.async_loop import AsyncConfig, AsyncTrainer
from repro.rl.trainer import RLConfig, Trainer
from repro.serving.faults import FaultEvent, FaultPlan
from repro.serving.rollout_service import WeightSync


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs.reset()
    yield
    obs.reset()


def _make_trainer(algo="grpo"):
    cfg = ModelConfig(name="tiny", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=VOCAB_SIZE,
                      max_seq_len=128)
    problems = generate_problems(MathTaskConfig(num_problems=8, max_operand=4))
    ds = PromptDataset(problems, max_prompt_len=10)
    rl = RLConfig(algo=algo, group_size=2, prompts_per_batch=4,
                  max_new_tokens=6, optim=AdamWConfig(lr=1e-3),
                  max_resample_rounds=1)
    spec = SpecConfig(variant="spec", lenience=math.e ** 0.5)
    return Trainer(cfg, rl, spec, ds, jax.random.PRNGKey(0))


def _fast_sync(max_attempts=3):
    return WeightSync(BackoffConfig(base=0.0, max_attempts=max_attempts),
                      sleep=lambda d: None)


# ------------------------------------------------------- determinism (K=0)

def test_k0_is_token_and_loss_identical_to_sync():
    steps = 3
    tr_sync = _make_trainer()
    sync_metrics = [tr_sync.train_step() for _ in range(steps)]

    at = AsyncTrainer(_make_trainer(),
                      AsyncConfig(staleness_window=0, buffer_capacity=2,
                                  schedule="pc"), sync=_fast_sync())
    async_metrics = at.run(steps)

    for ms, ma in zip(sync_metrics, async_metrics):
        assert ms["loss"] == ma["loss"], (ms["loss"], ma["loss"])
        assert ms["reward_mean"] == ma["reward_mean"]
    np.testing.assert_array_equal(np.asarray(tr_sync.last_rb.response),
                                  np.asarray(at.trainer.last_rb.response))
    for a, b in zip(jax.tree.leaves(tr_sync.params),
                    jax.tree.leaves(at.trainer.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert at.exact_steps == steps and at.is_steps == 0
    assert at.reverified == 0 and at.mode == "async"


# --------------------------------------------------- staleness window paths

def test_stale_within_window_gets_is_correction():
    # "ppcc": two collections land before each pair of optimizer steps, so
    # the second consumed trajectory is one version behind the policy
    at = AsyncTrainer(_make_trainer(),
                      AsyncConfig(staleness_window=2, buffer_capacity=4,
                                  schedule="ppcc"), sync=_fast_sync())
    out = at.run(4)
    assert at.exact_steps >= 1 and at.is_steps >= 1
    assert at.reverified == 0                 # window covers everything
    corrected = [m for m in out if m["staleness"] > 0]
    assert corrected and all("is_weight_mean" in m for m in corrected)
    assert all(np.isfinite(m["loss"]) for m in out)


def test_beyond_window_reverifies_instead_of_dropping():
    at = AsyncTrainer(_make_trainer(),
                      AsyncConfig(staleness_window=0, buffer_capacity=4,
                                  schedule="ppcc"), sync=_fast_sync())
    out = at.run(4)
    assert at.reverified >= 1                 # stale ⇒ re-verified, not shed
    assert at.buffer.shed == 0
    rev = [m for m in out if m.get("reverified")]
    assert rev and all(np.isfinite(m["loss"]) for m in rev)
    # re-verified steps still train on rewards computed under the fresh
    # response (the metrics schema matches the sync trainer's)
    assert all("reward_mean" in m and "collect_time" in m for m in out)


# ------------------------------------------------------- degradation ladder

def test_persistent_sync_failure_walks_the_ladder_to_sync():
    ws = _fast_sync(max_attempts=2)
    ws.fail_next(10 ** 6)                     # every publish attempt fails
    at = AsyncTrainer(_make_trainer(),
                      AsyncConfig(staleness_window=1, buffer_capacity=2,
                                  hard_staleness_cap=2, schedule="pc"),
                      sync=ws)
    out = at.run(8)
    assert len(out) == 8                      # degraded, never crashed
    assert at.mode == "sync" and at.degradations == 2
    assert at.sync_steps >= 1
    reg = obs.get_registry().as_dict()
    assert reg["async.degradation_level"] == 2.0
    assert reg["async.sync_failures"] >= 1
    assert reg["async.sync_retries"] >= 1
    # the service kept serving its last good version throughout
    assert at.service.version == 0


# ----------------------------------------------- failure-domain isolation

def test_seeded_chaos_producer_kill_plus_failed_sync():
    plan = FaultPlan([FaultEvent("kill", at_step=2),
                      FaultEvent("stall", at_step=4, count=1)])
    ws = _fast_sync(max_attempts=2)
    at = AsyncTrainer(_make_trainer(),
                      AsyncConfig(staleness_window=2, buffer_capacity=4,
                                  schedule="pc"),
                      faults=plan, sync=ws)
    ws.fail_next(2)                           # one publish fails fully
    out = at.run(6)
    assert len(out) == 6                      # completed despite the chaos
    assert at.producer_restarts == 1          # kill stayed in its domain
    assert at.service.stalled_ticks == 1
    assert ws.failures == 1
    assert all(np.isfinite(m["loss"]) for m in out)
    reg = obs.get_registry().as_dict()
    assert reg["async.producer_restarts"] == 1.0
    assert reg["async.sync_failures"] == 1.0
    # the pair degraded gracefully instead of dropping work
    at.buffer.check_invariants()


# --------------------------------------------------- exact kill-and-resume

def test_kill_and_resume_restores_buffer_and_version_state(tmp_path):
    acfg = AsyncConfig(staleness_window=1, buffer_capacity=4,
                       schedule="ppc")
    at = AsyncTrainer(_make_trainer(), acfg, sync=_fast_sync())
    at.run(2)                                 # leaves entries in the buffer
    assert len(at.buffer) >= 1
    at.save(str(tmp_path))

    at2 = AsyncTrainer(_make_trainer(), acfg, sync=_fast_sync())
    assert at2.restore(str(tmp_path))

    # byte-identical buffer/version/service state
    s1, s2 = at.state_dict(), at2.state_dict()
    f1, t1 = jax.tree.flatten(s1)
    f2, t2 = jax.tree.flatten(s2)
    assert t1 == t2
    for a, b in zip(f1, f2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert at2.version == at.version
    assert at2.service.version == at.service.version
    assert len(at2.buffer) == len(at.buffer)

    # ...and the continuation is identical too (shared-RNG replay)
    m1 = at.run(2)
    m2 = at2.run(2)
    assert [m["loss"] for m in m1] == [m["loss"] for m in m2]
    np.testing.assert_array_equal(np.asarray(at.trainer.last_rb.response),
                                  np.asarray(at2.trainer.last_rb.response))


def test_restore_on_empty_dir_is_a_fresh_start(tmp_path):
    at = AsyncTrainer(_make_trainer(), AsyncConfig(), sync=_fast_sync())
    assert not at.restore(str(tmp_path / "nothing"))
