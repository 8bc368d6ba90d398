"""Batched autoregressive generation and teacher-forced scoring.

The engine is built from two explicit, composable stages (see DESIGN.md §3):

* **prefill** — one forward over the (left-padded) prompt that populates the
  dense KV caches and yields the seed logits for the first sampled token;
* **decode** — a single ``lax.while_loop`` with per-row done flags that
  extends the caches one token at a time.

``generate`` = prefill ∘ decode and serves vanilla rollouts as well as the
legacy two-pass SPEC-RL continuation (caller concatenates prompt ⊕ verified
prefix into the "prompt").  ``resume_from_cache`` is the decode stage alone:
it starts the while_loop from an already-populated cache, per-row start
positions and seed logits, which is how the one-pass speculative path
continues straight out of verification with zero redundant prefill.
Left-padded batches, dense caches — the TPU-idiomatic replacement for vLLM's
continuous batching (see DESIGN.md §3).

Observability (DESIGN.md §11): ``generate`` and ``resume_from_cache`` are
themselves ``jax.jit`` programs, so the §11 tracer deliberately does NOT
reach inside them — host-side tracer calls traced into the jit graph would
either fail or bake ops into the compiled program, violating the
zero-overhead contract.  Their timings are spanned at the call sites
(core/spec_rollout opens the 'decode'/'generate' stage spans around its
existing ``block_until_ready`` boundaries), the decode loop's trip count
comes back as the ``steps`` output, and the §9 drafted loops — which ARE
host-driven — carry their own per-macro-step spans.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models.config import ModelConfig

from .sampling import entropy_of, logprobs_of, sample, split_key

PAD = 0


def positions_from_mask(mask) -> jnp.ndarray:
    """mask: (B, T) bool -> positions (B, T) int32, -1 where invalid."""
    pos = jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1
    return jnp.where(mask, pos, -1)


@dataclass(frozen=True)
class GenerateConfig:
    max_new_tokens: int = 64
    temperature: float = 1.0
    top_p: float = 1.0
    eos_id: int = 2
    pad_id: int = PAD


def _model_extras(model_kwargs):
    return {k: model_kwargs.get(k) for k in
            ("encoder_out", "encoder_positions")}


@functools.partial(jax.jit, static_argnames=("cfg", "gen", "mesh"))
def generate(params, cfg: ModelConfig, gen: GenerateConfig, prompt, prompt_mask,
             key, initial_done=None, row_budget=None, mesh=None,
             **model_kwargs) -> Dict[str, jnp.ndarray]:
    """prompt: (B, P) int32 left-padded; prompt_mask: (B, P) bool.

    initial_done: optional (B,) bool — rows that must not decode at all
    (SPEC-RL full-reuse rows).  row_budget: optional (B,) int32 — per-row max
    generated tokens (SPEC-RL continuation budget = max_resp - prefix_len).
    mesh: optional live Mesh (static) — the KV caches are constrained
    batch-over-data / heads-over-model and decode attention runs inside the
    §8 shard_map boundary; with sharded params/inputs the whole program
    compiles SPMD.  ``None`` is the single-device path, bit-for-bit the
    pre-mesh behaviour.

    Returns dict with:
      tokens     (B, N) generated tokens (pad after eos)
      logprobs   (B, N) behaviour log-probs of generated tokens
      length     (B,)   #generated tokens per row (including eos)
      n_generated ()    total generated tokens (the paper's "Tokens" metric)
      steps      ()     iterations the decode while_loop ran
      moe_held_tokens () (MoE trunks) the loop's routed assignments of
                        valid tokens that land on held experts, summed
                        over steps and MoE layers
    """
    B, P = prompt.shape
    N = gen.max_new_tokens
    positions = positions_from_mask(prompt_mask)
    extras = _model_extras(model_kwargs)
    prefix_embeds = model_kwargs.get("prefix_embeds")

    cache_len = P + N + (prefix_embeds.shape[1] if prefix_embeds is not None else 0)
    caches = M.init_cache(cfg, B, cache_len)
    if mesh is not None:
        from repro.distributed.mesh import constrain_caches
        caches = constrain_caches(cfg, caches, mesh)

    if prefix_embeds is not None:
        Pv = prefix_embeds.shape[1]
        vis_pos = jnp.broadcast_to(jnp.arange(Pv, dtype=jnp.int32), (B, Pv))
        positions_full = jnp.concatenate([vis_pos, jnp.where(
            positions >= 0, positions + Pv, -1)], axis=1)
        logits, caches = M.prefill(params, cfg, prompt, positions_full, caches,
                                   prefix_embeds=prefix_embeds, **extras)
        pos_offset = Pv
        write_offset = P + Pv
        # vision slots [0, Pv) are live ahead of the prompt's left padding,
        # so the context is not contiguous from a single start slot
        kv_start = None
    else:
        logits, caches = M.prefill(params, cfg, prompt, positions, caches, **extras)
        pos_offset = 0
        write_offset = P
        kv_start = P - prompt_mask.sum(axis=1).astype(jnp.int32)

    next_pos = prompt_mask.sum(axis=1).astype(jnp.int32) + pos_offset  # (B,)
    return _decode_loop(params, cfg, gen, caches, logits[:, -1], next_pos,
                        write_offset, key, initial_done, row_budget, extras,
                        kv_start=kv_start, mesh=mesh)


def _decode_loop(params, cfg: ModelConfig, gen: GenerateConfig, caches,
                 seed_logits, next_pos, write_offset, key,
                 initial_done, row_budget, extras,
                 kv_start=None, mesh=None) -> Dict[str, jnp.ndarray]:
    """The decode stage: sample from ``seed_logits`` then run the while_loop.

    caches: populated KV caches whose slots [0, write_offset) hold the
    context; seed_logits: (B, V) logits of the first token to sample;
    next_pos: (B,) position value of that first token.  Key-split order is
    identical whether entered via ``generate`` or ``resume_from_cache`` so
    the two-pass and one-pass SPEC-RL paths are sample-for-sample exact.

    ``key`` may be (2,) (batched sampling) or (B, 2) per-row keys; with
    per-row keys row b's token stream depends only on its own key, which is
    the invariant the serving slot scheduler's step loop mirrors split for
    split (see serving/engine_loop.py and DESIGN.md §6).
    """
    B = seed_logits.shape[0]
    N = gen.max_new_tokens
    # MoE trunks also count the loop's routed assignments on held experts
    count_held = cfg.num_experts > 0
    key, sub = split_key(key)
    tok0, lp0 = sample(sub, seed_logits, gen.temperature, gen.top_p)

    tokens_buf = jnp.full((B, N), gen.pad_id, jnp.int32)
    lp_buf = jnp.zeros((B, N), jnp.float32)

    def cond(state):
        step, done, *_ = state
        return (step < N) & ~jnp.all(done)

    def body(state):
        (step, done, cur_tok, cur_lp, next_pos, caches, tokens_buf, lp_buf,
         count, key), held = state[:10], state[10:]
        tok_store = jnp.where(done, gen.pad_id, cur_tok)
        lp_store = jnp.where(done, 0.0, cur_lp)
        tokens_buf = jax.lax.dynamic_update_index_in_dim(
            tokens_buf, tok_store, step, axis=1)
        lp_buf = jax.lax.dynamic_update_index_in_dim(lp_buf, lp_store, step, axis=1)
        count = count + (~done).astype(jnp.int32)
        done_next = done | (cur_tok == gen.eos_id) | (count >= budget)

        # live cache extent: [kv_start, write_offset + step] — the dead
        # left padding in front of the context and the unwritten tail are
        # both skipped by the flash-decode kernel
        out = M.decode_step(
            params, cfg, tok_store[:, None],
            jnp.where(done[:, None], -1, next_pos[:, None]),
            caches, write_offset + step,
            kv_length=write_offset + 1 + step, kv_start=kv_start,
            mesh=mesh, with_aux=count_held, **extras)
        logits, caches = out[:2]
        if count_held:
            held = (held[0] + out[2]["moe_held_tokens"],)
        key, sub = split_key(key)
        nxt, nlp = sample(sub, logits[:, 0], gen.temperature, gen.top_p)
        return (step + 1, done_next, nxt, nlp, next_pos + 1, caches,
                tokens_buf, lp_buf, count, key) + held

    done0 = jnp.zeros((B,), bool) if initial_done is None else initial_done
    budget = jnp.full((B,), N, jnp.int32) if row_budget is None else \
        row_budget.astype(jnp.int32)
    done0 = done0 | (budget <= 0)
    state = (jnp.array(0), done0, tok0, lp0, next_pos, caches,
             tokens_buf, lp_buf, jnp.zeros((B,), jnp.int32), key)
    if count_held:
        state += (jnp.zeros((), jnp.int32),)
    final = jax.lax.while_loop(cond, body, state)
    steps, _, _, _, _, _, tokens_buf, lp_buf, length, _ = final[:10]
    out = {
        "tokens": tokens_buf,
        "logprobs": lp_buf,
        "length": length,
        "n_generated": length.sum(),
        "steps": steps,
    }
    if count_held:
        out["moe_held_tokens"] = final[10]
    return out


@functools.partial(jax.jit, static_argnames=("cfg", "gen", "write_offset",
                                             "mesh"))
def resume_from_cache(params, cfg: ModelConfig, gen: GenerateConfig, caches,
                      seed_logits, next_pos, write_offset: int, key,
                      initial_done=None, row_budget=None, mesh=None,
                      **model_kwargs) -> Dict[str, jnp.ndarray]:
    """Continue decoding from an existing cache — the one-pass SPEC-RL entry.

    caches: decode caches whose slots [0, write_offset) already hold
    [left-aligned prompt ⊕ accepted prefix] (see model.realign_decode_cache);
    seed_logits: (B, V) logits of the last accepted (or last prompt) token;
    next_pos: (B,) int32 = prompt_len + n, the position the first continued
    token will occupy.  Returns the same dict as ``generate``.

    Bit-compatible with ``generate`` on the left-aligned layout: feeding the
    same PRNG key to either entry point yields the same key-split sequence,
    so continuation tokens/logprobs agree sample-for-sample.
    """
    extras = _model_extras(model_kwargs)
    next_pos = next_pos.astype(jnp.int32)
    if mesh is not None:
        from repro.distributed.mesh import constrain_caches
        caches = constrain_caches(cfg, caches, mesh)
    # compacted layout (§3): row b's context is contiguous in
    # [write_offset - next_pos[b], write_offset) — a short accepted prefix
    # decodes over its live extent, not the allocated verify width
    return _decode_loop(params, cfg, gen, caches, seed_logits,
                        next_pos, write_offset, key,
                        initial_done, row_budget, extras,
                        kv_start=write_offset - next_pos, mesh=mesh)


@functools.partial(jax.jit, static_argnames=("cfg", "temperature", "top_p",
                                             "return_entropy"))
def score(params, cfg: ModelConfig, tokens, mask, *, temperature: float = 1.0,
          top_p: float = 1.0, return_entropy: bool = False, **model_kwargs):
    """Teacher-forced scoring: log-prob of every token given its prefix.

    tokens: (B, L) left-padded full sequences; mask: (B, L) bool validity.
    Returns dict with ``logprobs`` (B, L) — entry t is the log-prob of
    tokens[:, t] under the sampling distribution given tokens[:, :t]
    (0 where mask is False or t is the first valid token), and optionally
    ``entropy`` (B, L).

    This single pass is SPEC-RL's *verification* forward (p_curr over the
    draft) and doubles as the PPO old-log-prob computation.
    """
    extras = _model_extras(model_kwargs)
    positions = positions_from_mask(mask)
    logits, _ = M.forward(params, cfg, tokens, positions,
                          prefix_embeds=model_kwargs.get("prefix_embeds"),
                          **extras)
    # logits[:, t] predicts tokens[:, t+1]
    lp_next = logprobs_of(logits[:, :-1], tokens[:, 1:], temperature, top_p)
    lp = jnp.concatenate([jnp.zeros_like(lp_next[:, :1]), lp_next], axis=1)
    # valid only where both target and its predecessor are valid
    valid = mask & jnp.concatenate([jnp.zeros_like(mask[:, :1]), mask[:, :-1]],
                                   axis=1)
    out = {"logprobs": jnp.where(valid, lp, 0.0), "valid": valid}
    if return_entropy:
        ent = entropy_of(logits[:, :-1], temperature)
        ent = jnp.concatenate([jnp.zeros_like(ent[:, :1]), ent], axis=1)
        out["entropy"] = jnp.where(valid, ent, 0.0)
    return out
