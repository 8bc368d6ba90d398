"""Serving demo: batched requests against the engine — prefill + decode with
per-row early stopping, plus a speculative *re-serve* pass that reuses a
previous response as the draft (the SPEC-RL mechanism applied to serving:
answer regeneration after a small model update).

    PYTHONPATH=src python examples/serve_batch.py
"""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.verify import verify_drafts
from repro.data.dataset import PromptDataset
from repro.data.tokenizer import VOCAB_SIZE, decode
from repro.engine.generate import GenerateConfig, generate
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.rewards.mathgen import MathTaskConfig, generate_problems


def main():
    cfg = ModelConfig(name="serve", num_layers=2, d_model=96, num_heads=4,
                      num_kv_heads=2, d_ff=192, vocab_size=VOCAB_SIZE,
                      max_seq_len=128)
    params = M.init_lm(jax.random.PRNGKey(0), cfg)

    problems = generate_problems(MathTaskConfig(num_problems=8, max_operand=9))
    ds = PromptDataset(problems, max_prompt_len=10)
    batch = ds.sample_batch(__import__("random").Random(0), 8, 1)
    prompts = jnp.asarray(batch.tokens)
    mask = jnp.asarray(batch.mask)
    gen = GenerateConfig(max_new_tokens=16, temperature=1.0)

    t0 = time.time()
    out = generate(params, cfg, gen, prompts, mask, jax.random.PRNGKey(1))
    jax.block_until_ready(out["tokens"])
    t_first = time.time() - t0
    print(f"batched serve: {int(out['n_generated'])} tokens "
          f"in {t_first:.2f}s")
    for i in range(4):
        txt = decode(np.asarray(out["tokens"][i, :out["length"][i]]))
        print(f"  [{batch.problem_ids[i]}] "
              f"{problems[batch.problem_ids[i]].prompt_text!r} -> {txt!r}")

    # simulate a small policy update, then re-serve speculatively
    updated = jax.tree.map(
        lambda x: x + 0.01 * jax.random.normal(jax.random.PRNGKey(2),
                                               x.shape).astype(x.dtype),
        params)
    t0 = time.time()
    ver = verify_drafts(updated, cfg, prompts, mask, out["tokens"],
                        out["logprobs"], out["length"], jax.random.PRNGKey(3),
                        math.log(math.e ** 0.5))
    n = ver["n"]
    jax.block_until_ready(n)
    reused = int(n.sum())
    total = int(out["length"].sum())
    print(f"\nspeculative re-serve after update: verified prefix "
          f"{reused}/{total} tokens ({100 * reused / max(total, 1):.0f}% "
          f"reused) in {time.time() - t0:.2f}s verification")
    print("per-request verified prefix:", np.asarray(n).tolist())


if __name__ == "__main__":
    main()
