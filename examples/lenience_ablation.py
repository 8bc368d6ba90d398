"""Lenience ablation (paper Table 3 / Fig. 4 at CPU scale).

Sweeps l over the paper's grid, reporting generated tokens, token-level
speedup vs vanilla, verified-prefix length, and reward — the tradeoff curve
that motivates moderate lenience.

    PYTHONPATH=src python examples/lenience_ablation.py --steps 6
"""
import argparse
import math

import jax

from repro.core import SpecConfig
from repro.data.dataset import PromptDataset
from repro.data.tokenizer import VOCAB_SIZE
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamWConfig
from repro.rewards.mathgen import MathTaskConfig, generate_problems
from repro.rl.trainer import RLConfig, Trainer

GRID = [("vanilla", None), ("l=1", 1.0), ("l=e^0.2", math.e ** 0.2),
        ("l=e^0.5", math.e ** 0.5), ("l=e^0.8", math.e ** 0.8),
        ("l=e^2.0", math.e ** 2.0), ("l=inf", float("inf"))]


def run_one(lenience, steps, seed=0):
    model = ModelConfig(name="abl", num_layers=2, d_model=96, num_heads=4,
                        num_kv_heads=2, d_ff=192, vocab_size=VOCAB_SIZE,
                        max_seq_len=128)
    problems = generate_problems(MathTaskConfig(num_problems=12,
                                                max_operand=9))
    ds = PromptDataset(problems, max_prompt_len=10)
    rl = RLConfig(algo="grpo", group_size=4, prompts_per_batch=4,
                  max_new_tokens=10, optim=AdamWConfig(lr=1e-3))
    if lenience is None:
        spec = SpecConfig(variant="off")
    elif math.isinf(lenience):
        spec = SpecConfig(variant="full")
    else:
        spec = SpecConfig(variant="spec", lenience=lenience)
    tr = Trainer(model, rl, spec, ds, jax.random.PRNGKey(seed))
    rewards, prefixes = [], []
    for _ in range(steps):
        m = tr.train_step()
        rewards.append(m["reward_mean"])
        prefixes.append(m.get("verified_prefix_mean", 0.0))
    return dict(tokens=tr.total_generated_tokens,
                reward=sum(rewards[-3:]) / 3,
                prefix=sum(prefixes) / len(prefixes))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=6)
    args = p.parse_args()

    base_tokens = None
    print(f"{'setting':>9} {'tokens':>8} {'speedup':>8} {'prefix':>7} "
          f"{'reward':>7}")
    for name, l in GRID:
        r = run_one(l, args.steps)
        if base_tokens is None:
            base_tokens = r["tokens"]
        speed = base_tokens / max(r["tokens"], 1)
        print(f"{name:>9} {r['tokens']:8d} {speed:7.2f}x {r['prefix']:7.2f} "
              f"{r['reward']:7.3f}")


if __name__ == "__main__":
    main()
