"""Quickstart: GRPO + SPEC-RL on the synthetic verifiable-math task.

Trains a tiny model for a handful of steps and prints the paper's headline
metrics per step: generated tokens (the efficiency metric), verified-prefix
length, full-reuse ratio, reward.

    PYTHONPATH=src python examples/quickstart.py
"""
import math

import jax

from repro.core import SpecConfig
from repro.data.dataset import PromptDataset
from repro.data.tokenizer import VOCAB_SIZE
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamWConfig
from repro.rewards.mathgen import MathTaskConfig, generate_problems
from repro.rl.trainer import RLConfig, Trainer


def main():
    model = ModelConfig(name="quickstart", num_layers=2, d_model=96,
                        num_heads=4, num_kv_heads=2, d_ff=192,
                        vocab_size=VOCAB_SIZE, max_seq_len=128)
    problems = generate_problems(MathTaskConfig(num_problems=12,
                                                max_operand=9))
    dataset = PromptDataset(problems, max_prompt_len=10)
    rl = RLConfig(algo="grpo", group_size=4, prompts_per_batch=4,
                  max_new_tokens=10, optim=AdamWConfig(lr=1e-3))
    spec = SpecConfig(variant="spec", lenience=math.e ** 0.5)

    trainer = Trainer(model, rl, spec, dataset, jax.random.PRNGKey(0))
    print(f"{'step':>4} {'reward':>7} {'gen_tok':>8} {'reused':>7} "
          f"{'prefix':>7} {'full_reuse':>10}")
    for _ in range(8):
        m = trainer.train_step()
        print(f"{m['step']:4.0f} {m['reward_mean']:7.3f} "
              f"{m.get('n_generated', 0):8.0f} {m.get('n_reused', 0):7.0f} "
              f"{m.get('verified_prefix_mean', 0):7.2f} "
              f"{m.get('full_reuse_ratio', 0):10.2f}")
    print(f"\ntotal generated tokens: {trainer.total_generated_tokens}"
          f" (vanilla would regenerate everything each step)")
    print("cache:", trainer.cache.stats())


if __name__ == "__main__":
    main()
