"""Rollout-stage microbenchmark: verify / compact(prefill) / decode wall-time
split for the one-pass vs two-pass speculative engine paths, plus the
no-second-prefill op-count assertion.  Writes BENCH_rollout.json so future
PRs have a perf trajectory to regress against.

    PYTHONPATH=src python -m benchmarks.rollout_stages [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import RolloutCache, SpecConfig, rollout
from repro.data.tokenizer import VOCAB_SIZE
from repro.engine.generate import GenerateConfig
from repro.models import model as M
from repro.models.config import ModelConfig

from .common import emit

SIZES = [(4, 8, 16), (8, 16, 32), (4, 32, 64)]          # (B, P, N)
STAGES = ("verify_time", "compact_time", "decode_time", "assembly_time")
OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_rollout.json")


def _setup(B, P, N, seed=0):
    cfg = ModelConfig(name="bench", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=VOCAB_SIZE,
                      max_seq_len=max(256, P + 2 * N))
    params = M.init_lm(jax.random.PRNGKey(seed), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, P), 3,
                                VOCAB_SIZE)
    mask = jnp.ones((B, P), bool)
    gen = GenerateConfig(max_new_tokens=N, eos_id=VOCAB_SIZE - 1)
    return cfg, params, prompt, mask, gen


def _spec(one_pass: str) -> SpecConfig:
    # lenience < 1 with an unchanged policy gives per-token accept prob == l,
    # i.e. a realistic partial-acceptance mix of reused and regenerated
    # tokens (full acceptance would degenerate decode_time to ~0).
    return SpecConfig(variant="spec", lenience=0.8, one_pass=one_pass)


def _time_path(cfg, params, prompt, mask, gen, cache, one_pass: str,
               iters: int):
    """Mean per-stage seconds over ``iters`` warm rollout steps.

    Each iteration re-verifies the same drafts (fresh cache copy) so the
    accepted-prefix length — and therefore the work — is held constant."""
    ids = list(range(prompt.shape[0]))
    spec = _spec(one_pass)
    acc = {s: 0.0 for s in STAGES}
    reused = generated = 0
    rollout(params, cfg, gen, spec, prompt, mask, ids, copy.deepcopy(cache),
            jax.random.PRNGKey(2), 1)            # jit warmup
    for i in range(iters):
        rb = rollout(params, cfg, gen, spec, prompt, mask, ids,
                     copy.deepcopy(cache), jax.random.PRNGKey(2), 1)
        for s in STAGES:
            acc[s] += rb.metrics[s]
        reused += rb.metrics["n_reused"]
        generated += rb.metrics["n_generated"]
    out = {s: acc[s] / iters for s in STAGES}
    out["total"] = sum(out.values())
    out["n_reused"] = reused / iters
    out["n_generated"] = generated / iters
    return out


def _assert_single_prefill(cfg, params, prompt, mask):
    """Op-count proof that one-pass forwards prompt ⊕ prefix exactly once."""
    ids = list(range(prompt.shape[0]))
    small = GenerateConfig(max_new_tokens=4)
    cache = RolloutCache()
    rollout(params, cfg, small, _spec("off"), prompt, mask, ids, cache,
            jax.random.PRNGKey(0), 0)           # seed drafts
    with jax.disable_jit():
        M.reset_op_counts()
        rollout(params, cfg, small, _spec("on"), prompt, mask, ids,
                copy.deepcopy(cache), jax.random.PRNGKey(2), 1)
        assert M.OP_COUNTS["prefill"] == 1, M.OP_COUNTS
        assert M.OP_COUNTS["forward"] == 0, M.OP_COUNTS
        one = dict(M.OP_COUNTS)
        M.reset_op_counts()
        rollout(params, cfg, small, _spec("off"), prompt, mask, ids,
                copy.deepcopy(cache), jax.random.PRNGKey(2), 1)
        assert M.OP_COUNTS["prefill"] + M.OP_COUNTS["forward"] == 2, M.OP_COUNTS
    emit("rollout_stages/op_count", 0.0,
         f"one_pass_prefill={one['prefill']};one_pass_forward={one['forward']}")


def run(smoke: bool = False, out_path: str = OUT_PATH) -> None:
    sizes = SIZES[:1] if smoke else SIZES
    iters = 2 if smoke else 5
    record = {"backend": jax.default_backend(), "iters": iters, "sizes": []}
    for B, P, N in sizes:
        cfg, params, prompt, mask, gen = _setup(B, P, N)
        cache = RolloutCache()
        rollout(params, cfg, gen, _spec("off"), prompt, mask,
                list(range(B)), cache, jax.random.PRNGKey(0), 0)  # seed drafts
        row = {"B": B, "P": P, "N": N}
        for label, flag in (("one_pass", "on"), ("two_pass", "off")):
            t = _time_path(cfg, params, prompt, mask, gen, cache, flag, iters)
            row[label] = t
            emit(f"rollout_stages/{label}", t["total"] * 1e6,
                 f"B={B};P={P};N={N};" + ";".join(
                     f"{s.replace('_time','')}={t[s]*1e3:.2f}ms"
                     for s in STAGES) + f";reused={t['n_reused']:.1f}")
        row["speedup_total"] = row["two_pass"]["total"] / max(
            row["one_pass"]["total"], 1e-9)
        record["sizes"].append(row)
    _assert_single_prefill(*_setup(*sizes[0])[:4])
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    emit("rollout_stages/json", 0.0, out_path)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="smallest size only, 2 iters")
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args()
    run(smoke=args.smoke, out_path=args.out)
