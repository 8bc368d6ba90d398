"""Trainer watchdog (DESIGN.md §10): snapshot-on-healthy, restore-last-good
on poisoned steps, skip (not replay) the poisoned batch."""
import math

import jax
import numpy as np
import pytest

from repro.checkpoint.io import read_latest
from repro.core import SpecConfig
from repro.data.dataset import PromptDataset
from repro.data.tokenizer import VOCAB_SIZE
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamWConfig
from repro.rewards.mathgen import MathTaskConfig, generate_problems
from repro.rl.trainer import RLConfig, Trainer
from repro.rl.watchdog import TrainWatchdog, WatchdogConfig


def _make_trainer(watchdog=None, algo="grpo"):
    cfg = ModelConfig(name="tiny", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=VOCAB_SIZE,
                      max_seq_len=128)
    problems = generate_problems(MathTaskConfig(num_problems=8, max_operand=4))
    ds = PromptDataset(problems, max_prompt_len=10)
    rl = RLConfig(algo=algo, group_size=2, prompts_per_batch=4,
                  max_new_tokens=6, optim=AdamWConfig(lr=1e-3),
                  max_resample_rounds=1)
    spec = SpecConfig(variant="spec", lenience=math.e ** 0.5)
    return Trainer(cfg, rl, spec, ds, jax.random.PRNGKey(0),
                   watchdog=watchdog)


def test_healthy_steps_snapshot_on_cadence(tmp_path):
    wd = TrainWatchdog(WatchdogConfig(checkpoint_dir=str(tmp_path),
                                      snapshot_every=2))
    tr = _make_trainer(watchdog=wd)
    metrics = [tr.train_step() for _ in range(3)]
    # first healthy step snapshots unconditionally, then every cadence-th
    assert wd.snapshots >= 2
    assert read_latest(str(tmp_path)) is not None
    assert metrics[-1]["watchdog_snapshots"] == float(wd.snapshots)
    assert metrics[-1]["watchdog_restores"] == 0.0


def test_poisoned_step_restores_last_good(tmp_path):
    wd = TrainWatchdog(WatchdogConfig(checkpoint_dir=str(tmp_path),
                                      snapshot_every=1))
    tr = _make_trainer(watchdog=wd)
    tr.train_step()
    good = jax.tree.map(np.asarray, tr.params)
    step_before = tr.step_idx

    # simulate a poisoned update landing on the params
    tr.params = jax.tree.map(lambda x: x * np.nan, tr.params)
    m = {"loss": float("nan"), "reward_mean": 0.0}
    wd.after_step(tr, m)

    assert m.get("watchdog_restored") == 1.0
    assert wd.nonfinite_steps == 1 and wd.restores == 1
    for a, b in zip(jax.tree.leaves(tr.params), jax.tree.leaves(good)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # step counter NOT rolled back: the poisoned batch is skipped, the
    # next step trains on fresh data with the restored params
    assert tr.step_idx == step_before
    m2 = tr.train_step()
    assert np.isfinite(m2["loss"])
    assert m2["watchdog_restores"] == 1.0


def test_stalled_rollout_counts_as_poisoned(tmp_path):
    wd = TrainWatchdog(WatchdogConfig(checkpoint_dir=str(tmp_path),
                                      snapshot_every=1, max_collect_time=0.5))
    tr = _make_trainer(watchdog=wd)
    tr.train_step()
    m = {"loss": 0.1, "reward_mean": 0.0, "collect_time": 10.0}
    wd.after_step(tr, m)
    assert wd.stalled_steps == 1 and wd.restores == 1
    assert m["watchdog_restored"] == 1.0


def test_restore_budget_exhaustion_raises(tmp_path):
    wd = TrainWatchdog(WatchdogConfig(checkpoint_dir=str(tmp_path),
                                      snapshot_every=1, max_restores=0))
    tr = _make_trainer(watchdog=wd)
    tr.train_step()
    with pytest.raises(RuntimeError, match="restore budget"):
        wd.after_step(tr, {"loss": float("nan")})


def test_poisoned_before_any_snapshot_skips(tmp_path):
    wd = TrainWatchdog(WatchdogConfig(checkpoint_dir=str(tmp_path)))
    tr = _make_trainer(watchdog=wd)
    m = {"loss": float("nan")}
    wd.after_step(tr, m)                        # nothing to restore yet
    assert wd.skipped_no_snapshot == 1 and wd.restores == 0
    assert "watchdog_restored" not in m


def test_restore_carries_cache_and_counters(tmp_path):
    """The rollout cache and generation counters travel with the snapshot:
    a restored trainer keeps its SPEC-RL reuse warm."""
    wd = TrainWatchdog(WatchdogConfig(checkpoint_dir=str(tmp_path),
                                      snapshot_every=1))
    tr = _make_trainer(watchdog=wd)
    tr.train_step()
    cached = sorted(tr.cache._store)
    gen_steps = tr.gen_steps
    tr.cache._store.clear()                     # simulated corruption
    wd.after_step(tr, {"loss": float("nan")})
    assert sorted(tr.cache._store) == cached
    assert tr.gen_steps == gen_steps


def test_service_stall_routes_through_restore(tmp_path):
    """§12: a stalled rollout *service* surfaces as the consumer waiting
    far past its normal fresh-trajectory cadence — same restore-last-good
    verdict as an in-process collect stall."""
    wd = TrainWatchdog(WatchdogConfig(checkpoint_dir=str(tmp_path),
                                      snapshot_every=1,
                                      max_service_wait=1.0))
    tr = _make_trainer(watchdog=wd)
    tr.train_step()
    good = jax.tree.map(np.asarray, tr.params)
    tr.params = jax.tree.map(lambda x: x * 2.0, tr.params)
    m = {"loss": 0.1, "reward_mean": 0.0, "service_wait_s": 5.0}
    wd.after_step(tr, m)
    assert wd.service_stalled_steps == 1 and wd.restores == 1
    assert m["watchdog_restored"] == 1.0
    for a, b in zip(jax.tree.leaves(tr.params), jax.tree.leaves(good)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_service_stall_adaptive_p95(tmp_path):
    """No absolute cap set: the adaptive p95 × mult detector arms off the
    run's own healthy trajectory waits and trips on the outlier."""
    wd = TrainWatchdog(WatchdogConfig(checkpoint_dir=str(tmp_path),
                                      snapshot_every=1, stall_p95_mult=10.0,
                                      stall_min_samples=4))
    tr = _make_trainer(watchdog=wd)
    tr.train_step()
    for i in range(5):                          # healthy waits ~10ms
        wd.after_step(tr, {"loss": 0.1, "reward_mean": 0.0,
                           "service_wait_s": 0.01 + 0.001 * i})
    assert wd.service_stalled_steps == 0
    m = {"loss": 0.1, "reward_mean": 0.0, "service_wait_s": 30.0}
    wd.after_step(tr, m)
    assert wd.service_stalled_steps == 1
    assert m["watchdog_service_wait_p95"] > 0


def test_staleness_gauge_blowout_is_a_service_stall(tmp_path):
    wd = TrainWatchdog(WatchdogConfig(checkpoint_dir=str(tmp_path),
                                      snapshot_every=1,
                                      max_service_staleness=4.0))
    tr = _make_trainer(watchdog=wd)
    tr.train_step()
    m = {"loss": 0.1, "reward_mean": 0.0, "service_staleness": 9.0}
    wd.after_step(tr, m)
    assert wd.service_stalled_steps == 1 and m["watchdog_restored"] == 1.0
