"""Disaggregated async rollout ↔ train benchmark (DESIGN.md §12).

Three legs over the same tiny trainer:

* **identity** — K=0 under the strict ``"pc"`` interleave must be loss-
  and token-identical to the synchronous trainer (asserted in-bench, the
  §12 determinism contract — a perf number from a wrong loop is worthless);
* **sync** — wall time per ``Trainer.train_step`` (collect + optimize in
  one process, the pre-§12 loop);
* **async** — the buffer is pre-filled by producer ticks, then wall time
  per ``consumer_step`` measures the optimization half alone: the collect
  stage has moved into the producer's failure domain, which is exactly the
  overlap a disaggregated deployment buys.  The consumed staleness
  distribution is recorded alongside.

``async_vs_sync_speedup`` = sync step wall / async consumer-step wall
(> 1 ⇔ collection dominates the step, the regime SPEC-RL targets).
Writes BENCH_async.json.

    PYTHONPATH=src python -m benchmarks.async_bench [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import time

import jax
import numpy as np

from repro.core import SpecConfig
from repro.data.dataset import PromptDataset
from repro.data.tokenizer import VOCAB_SIZE
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamWConfig
from repro.rewards.mathgen import MathTaskConfig, generate_problems
from repro.rl.async_loop import AsyncConfig, AsyncTrainer
from repro.rl.trainer import RLConfig, Trainer

from .common import emit

OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_async.json")


def _make_trainer(max_new_tokens: int, variant: str = "spec") -> Trainer:
    cfg = ModelConfig(name="bench", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=VOCAB_SIZE,
                      max_seq_len=128)
    problems = generate_problems(MathTaskConfig(num_problems=16,
                                                max_operand=9))
    ds = PromptDataset(problems, max_prompt_len=10)
    rl = RLConfig(algo="grpo", group_size=2, prompts_per_batch=4,
                  max_new_tokens=max_new_tokens, optim=AdamWConfig(lr=1e-3),
                  max_resample_rounds=1)
    spec = SpecConfig(variant=variant, lenience=math.e ** 0.5)
    return Trainer(cfg, rl, spec, ds, jax.random.PRNGKey(0))


def run(smoke: bool = False, out_path: str = OUT_PATH) -> dict:
    steps = 4 if smoke else 8
    toks = 6 if smoke else 12

    # ---- leg 0: the K=0 determinism contract, asserted in-bench --------
    tr_ref = _make_trainer(toks)
    ref = [tr_ref.train_step() for _ in range(3)]
    at0 = AsyncTrainer(_make_trainer(toks),
                       AsyncConfig(staleness_window=0, buffer_capacity=2,
                                   schedule="pc"))
    got = at0.run(3)
    for ms, ma in zip(ref, got):
        assert ms["loss"] == ma["loss"], \
            f"K=0 identity broken: {ms['loss']} != {ma['loss']}"
    np.testing.assert_array_equal(np.asarray(tr_ref.last_rb.response),
                                  np.asarray(at0.trainer.last_rb.response))
    emit("async/k0_identity", 0.0, f"{len(got)} steps bit-identical")

    # The perf legs run variant="off" (full generation each collect): the
    # disaggregation win is proportional to the collect stage's share of
    # the step, and SPEC-RL reuse at bench scale shrinks that share to
    # noise — "off" is the collection-dominated regime §12 targets.
    # ---- leg 1: synchronous wall per train step ------------------------
    tr_sync = _make_trainer(toks, variant="off")
    tr_sync.train_step()                              # compile warmup
    t0 = time.perf_counter()
    for _ in range(steps):
        tr_sync.train_step()
    t_sync = time.perf_counter() - t0

    # ---- leg 2: async consumer wall off a warm buffer ------------------
    at = AsyncTrainer(_make_trainer(toks, variant="off"),
                      AsyncConfig(staleness_window=steps + 4,
                                  buffer_capacity=steps + 5,
                                  # the timed leg batch-consumes with no
                                  # producer ticks in between, so service
                                  # staleness legitimately runs ahead —
                                  # park the ladder out of the way
                                  hard_staleness_cap=10 * steps,
                                  schedule="pc"))
    at.run(1)                                         # exact-path warmup
    for _ in range(steps + 3):                        # pre-fill: collection
        assert at.producer_tick()                     # happens off-step
    for _ in range(3):                                # warm BOTH optimize
        m = at.consumer_step()                        # branches (the first
        assert m is not None                          # stale one compiles
        if m["staleness"] > 0 and at.is_steps >= 2:   # the IS program)
            break
    metrics = []
    t0 = time.perf_counter()
    for _ in range(steps):
        m = at.consumer_step()
        assert m is not None, "warm buffer starved"
        metrics.append(m)
    t_async = time.perf_counter() - t0

    staleness = [m["staleness"] for m in metrics]
    assert at.reverified == 0, "window sized to keep this leg IS-only"
    assert at.mode == "async", at.mode

    record = {
        "backend": jax.default_backend(),
        "steps": steps, "max_new_tokens": toks,
        "k0_identity": True,                          # asserted above
        "sync": {"time_s": t_sync, "per_step_ms": t_sync / steps * 1e3},
        "async": {
            "time_s": t_async, "per_step_ms": t_async / steps * 1e3,
            "exact_steps": int(at.exact_steps),
            "is_steps": int(at.is_steps),
            "staleness": {"min": float(min(staleness)),
                          "max": float(max(staleness)),
                          "mean": float(np.mean(staleness))},
            **{k: int(v) for k, v in at.buffer.counters().items()},
        },
        # > 1 ⇔ the collect stage dominates the step; disaggregation
        # moves it off the optimizer's critical path
        "async_vs_sync_speedup": t_sync / max(t_async, 1e-9),
    }
    emit("async/sync_step", t_sync / steps * 1e6, f"{steps} steps")
    emit("async/consumer_step", t_async / steps * 1e6,
         f"stale_mean={record['async']['staleness']['mean']:.1f}")
    emit("async/speedup", 0.0,
         f"{record['async_vs_sync_speedup']:.2f}x")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    emit("async/json", 0.0, out_path)
    return record


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fewer steps, smaller generation budget")
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args()
    run(smoke=args.smoke, out_path=args.out)
