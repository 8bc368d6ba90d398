"""End-to-end driver (deliverable b): train a model with RLVR + SPEC-RL for a
few hundred steps, with checkpointing, eval, and a vanilla-baseline
comparison mode.

Default is a CPU-budget model; ``--model 100m`` selects a ~100M-parameter
qwen3-style backbone (the assignment's e2e scale — practical on accelerators,
slow but runnable on CPU).

    PYTHONPATH=src python examples/train_spec_rl.py --steps 200
    PYTHONPATH=src python examples/train_spec_rl.py --variant off   # baseline
    PYTHONPATH=src python examples/train_spec_rl.py --model 100m --steps 300
"""
import argparse
import json
import math
import os
import time

import jax
import numpy as np

from repro.checkpoint.io import save_pytree, save_rollout_cache
from repro.core import SpecConfig
from repro.data.dataset import PromptDataset
from repro.data.tokenizer import VOCAB_SIZE, decode
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamWConfig
from repro.rewards.mathgen import MathTaskConfig, generate_problems
from repro.rewards.verifier import batch_rewards
from repro.rl.trainer import RLConfig, Trainer

MODELS = {
    "tiny": ModelConfig(name="tiny", num_layers=2, d_model=96, num_heads=4,
                        num_kv_heads=2, d_ff=192, vocab_size=VOCAB_SIZE,
                        max_seq_len=128),
    "20m": ModelConfig(name="20m", num_layers=6, d_model=384, num_heads=6,
                       num_kv_heads=2, d_ff=1152, vocab_size=VOCAB_SIZE,
                       qk_norm=True, max_seq_len=256),
    "100m": ModelConfig(name="100m", num_layers=12, d_model=768, num_heads=12,
                        num_kv_heads=4, d_ff=2304, vocab_size=VOCAB_SIZE,
                        qk_norm=True, max_seq_len=512),
}


def evaluate(trainer: Trainer, n_prompts: int = 16) -> float:
    """Greedy eval on held-out problems (exact-match accuracy)."""
    from repro.engine.generate import GenerateConfig, generate
    problems = generate_problems(MathTaskConfig(num_problems=n_prompts,
                                                max_operand=9, seed=999))
    ds = PromptDataset(problems, max_prompt_len=10)
    batch = ds.sample_batch(__import__("random").Random(0), n_prompts, 1)
    gen = GenerateConfig(max_new_tokens=trainer.rl.max_new_tokens,
                         temperature=0.0)
    out = generate(trainer.params, trainer.cfg, gen,
                   jax.numpy.asarray(batch.tokens),
                   jax.numpy.asarray(batch.mask), jax.random.PRNGKey(0))
    r = batch_rewards(np.asarray(out["tokens"]), np.asarray(out["length"]),
                      batch.answers)
    return float(r.mean())


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", choices=sorted(MODELS), default="tiny")
    p.add_argument("--algo", choices=["grpo", "ppo", "dapo"], default="grpo")
    p.add_argument("--variant", choices=["spec", "off", "random", "delayed",
                                         "full"], default="spec")
    p.add_argument("--lenience", type=float, default=math.e ** 0.5)
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--problems", type=int, default=32)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--max-new-tokens", type=int, default=10)
    p.add_argument("--eval-every", type=int, default=20)
    p.add_argument("--out", default="runs/train_spec_rl")
    args = p.parse_args()

    model = MODELS[args.model]
    problems = generate_problems(MathTaskConfig(num_problems=args.problems,
                                                max_operand=9))
    dataset = PromptDataset(problems, max_prompt_len=10)
    rl = RLConfig(algo=args.algo, group_size=4, prompts_per_batch=8,
                  max_new_tokens=args.max_new_tokens,
                  optim=AdamWConfig(lr=args.lr))
    spec = SpecConfig(variant=args.variant, lenience=args.lenience)
    trainer = Trainer(model, rl, spec, dataset, jax.random.PRNGKey(0))

    os.makedirs(args.out, exist_ok=True)
    t0 = time.time()
    for i in range(args.steps):
        m = trainer.train_step()
        if i % 10 == 0:
            print(f"step {m['step']:4.0f} reward={m['reward_mean']:.3f} "
                  f"gen_tok={m.get('n_generated', 0):6.0f} "
                  f"reuse={m.get('n_reused', 0):6.0f} "
                  f"kl={m.get('approx_kl', 0):+.4f} "
                  f"ent={m.get('entropy', 0):.2f}", flush=True)
        if args.eval_every and (i + 1) % args.eval_every == 0:
            acc = evaluate(trainer)
            print(f"  eval@{i+1}: exact-match={acc:.3f}")

    acc = evaluate(trainer)
    wall = time.time() - t0
    print(f"\nfinal eval={acc:.3f}; total generated tokens="
          f"{trainer.total_generated_tokens}; wall={wall:.1f}s")
    save_pytree(os.path.join(args.out, "policy"), trainer.params,
                {"steps": args.steps, "algo": args.algo,
                 "variant": args.variant})
    save_rollout_cache(os.path.join(args.out, "rollouts"), trainer.cache)
    with open(os.path.join(args.out, "history.json"), "w") as f:
        json.dump(trainer.history, f, indent=1)
    print(f"checkpoint + history written to {args.out}/")


if __name__ == "__main__":
    main()
