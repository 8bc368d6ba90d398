"""Shared benchmark harness: one tiny backbone + identical shapes across all
benchmarks so jit caches are reused; CSV emission helpers.

The benchmarks reproduce the paper's MEASURABLE CLAIMS at CPU scale: token
reduction, speedup vs lenience, variant comparisons, diagnostics, diversity.
Token counts are exact (the paper's own primary efficiency metric);
wall-clock is reported for completeness but CPU timing is not the claim.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.core import SpecConfig
from repro.data.dataset import PromptDataset
from repro.data.tokenizer import VOCAB_SIZE
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamWConfig
from repro.rewards.mathgen import MathTaskConfig, generate_problems
from repro.rl.trainer import RLConfig, Trainer

ROWS: List[str] = []


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def bench_model_cfg() -> ModelConfig:
    return ModelConfig(name="bench", num_layers=2, d_model=64, num_heads=4,
                       num_kv_heads=2, d_ff=128, vocab_size=VOCAB_SIZE,
                       max_seq_len=128)


def bench_dataset(n: int = 12) -> PromptDataset:
    problems = generate_problems(MathTaskConfig(num_problems=n, max_operand=9))
    return PromptDataset(problems, max_prompt_len=10)


def make_trainer(algo: str = "grpo", variant: str = "spec",
                 lenience: float = math.e ** 0.5, seed: int = 0,
                 dataset: Optional[PromptDataset] = None,
                 max_new_tokens: int = 12) -> Trainer:
    cfg = bench_model_cfg()
    ds = dataset or bench_dataset()
    rl = RLConfig(algo=algo, group_size=2, prompts_per_batch=4,
                  max_new_tokens=max_new_tokens, optim=AdamWConfig(lr=5e-4),
                  max_resample_rounds=1)
    spec = SpecConfig(variant=variant, lenience=lenience)
    return Trainer(cfg, rl, spec, ds, jax.random.PRNGKey(seed))


def run_steps(tr: Trainer, n: int) -> Dict[str, float]:
    t0 = time.perf_counter()
    rollout_time = 0.0
    for _ in range(n):
        m = tr.train_step()
        rollout_time += m.get("rollout_time", 0.0)   # the whole rollout call
    wall = time.perf_counter() - t0
    h = tr.history
    return {
        "tokens": tr.total_generated_tokens,
        "reward_last": float(np.mean([x["reward_mean"] for x in h[-2:]])),
        "wall_s": wall,
        "rollout_s": rollout_time,
        "steps": n,
        "entropy": float(np.mean([x.get("entropy", 0.0) for x in h])),
        "kl": float(np.mean([abs(x.get("approx_kl", 0.0)) for x in h])),
        "clip_frac": float(np.mean([x.get("clip_frac", 0.0) for x in h])),
        "prefix_mean": float(np.mean([x.get("verified_prefix_mean", 0.0)
                                      for x in h])),
        "full_reuse": float(np.mean([x.get("full_reuse_ratio", 0.0)
                                     for x in h])),
    }
