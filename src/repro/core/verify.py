"""SPEC-RL draft verification (Algorithm 1, the jit'd device side).

One teacher-forced forward of the current policy over prompt ⊕ draft yields
``p_curr``; the fused accept/first-reject reduction (Pallas kernel on TPU,
its oracle elsewhere) yields the rejection position ``n`` per row.

Two flavours:

* ``verify_drafts``      — scoring-only (discards activations); feeds the
  legacy two-pass path and non-cache callers.
* ``verify_and_prefill`` — *prefilling* verification: the same forward runs
  through ``M.prefill`` so the KV caches come out populated, alongside the
  per-row seed logits at the last accepted token.  Combined with
  model.realign_decode_cache + engine.resume_from_cache this makes the whole
  speculative step a single pass over prompt ⊕ draft (DESIGN.md §3).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from repro.distributed.shard_wrap import sharded_spec_verify
from repro.engine.generate import positions_from_mask, score
from repro.engine.sampling import logprobs_of
from repro.kernels.spec_verify.ops import spec_verify
from repro.models import model as M
from repro.models.config import ModelConfig


def _accept_uniforms(key, B: int, N: int) -> jnp.ndarray:
    """Per-token acceptance uniforms u (B, N).

    key: (2,) — one stream for the whole batch — or (B, 2) per-row keys,
    where row b's uniforms depend only on its own key.  Per-row streams make
    the rejection index a per-request quantity, invariant to how requests
    are grouped into verification batches (serving spec-prefix admission,
    DESIGN.md §6)."""
    if jnp.ndim(key) == 2:
        return jax.vmap(lambda k: jax.random.uniform(k, (N,)))(key)
    return jax.random.uniform(key, (B, N))


@functools.partial(jax.jit, static_argnames=("cfg", "temperature", "top_p",
                                             "mesh"))
def verify_drafts(params, cfg: ModelConfig, prompt, prompt_mask,
                  draft_tokens, draft_logprobs, draft_len, key,
                  log_lenience, *, temperature: float = 1.0,
                  top_p: float = 1.0, mesh=None,
                  **model_kwargs) -> Dict[str, jnp.ndarray]:
    """prompt: (B, P) left-padded; draft_*: (B, N) right-padded.

    Returns:
      n            (B,) first-rejection position in [0, draft_len]
      lp_curr      (B, N) current-policy log-probs of draft tokens
      accept_rate  ()    fraction of draft tokens accepted
    """
    B, P = prompt.shape
    N = draft_tokens.shape[1]
    didx = jnp.arange(N, dtype=jnp.int32)[None, :]
    draft_mask = didx < draft_len[:, None]

    full = jnp.concatenate([prompt, jnp.where(draft_mask, draft_tokens, 0)], axis=1)
    mask = jnp.concatenate([prompt_mask, draft_mask], axis=1)
    sc = score(params, cfg, full, mask, temperature=temperature, top_p=top_p,
               **model_kwargs)
    lp_curr = sc["logprobs"][:, P:]                       # (B, N)

    u = _accept_uniforms(key, B, N)
    n = _spec_verify(mesh, lp_curr, draft_logprobs, u, draft_len,
                     log_lenience)

    total = jnp.maximum(draft_len.sum(), 1)
    accept_rate = n.sum() / total
    return {"n": n, "lp_curr": lp_curr, "accept_rate": accept_rate}


def _spec_verify(mesh, lp_curr, draft_logprobs, u, draft_len, log_lenience):
    """Dispatch the accept/first-reject kernel, via §8 shard_map on a mesh."""
    if mesh is not None:
        return sharded_spec_verify(mesh, lp_curr, draft_logprobs, u,
                                   draft_len, log_lenience)
    return spec_verify(lp_curr, draft_logprobs, u, draft_len, log_lenience)


@functools.partial(jax.jit, static_argnames=("cfg", "temperature", "top_p",
                                             "mesh"))
def verify_and_prefill(params, cfg: ModelConfig, prompt, prompt_mask,
                       draft_tokens, draft_logprobs, draft_len, key,
                       log_lenience, *, temperature: float = 1.0,
                       top_p: float = 1.0, mesh=None,
                       **model_kwargs) -> Dict[str, jnp.ndarray]:
    """Fused verification + engine prefill over [prompt | draft] (one pass).

    Same inputs and verification semantics as ``verify_drafts`` (identical
    token/mask/position layout and PRNG stream, so ``n`` and ``lp_curr``
    agree with the two-pass path), but the forward also populates decode
    caches sized W + N (W = P + N) so continuation never re-prefills.

    Extra returns on top of verify_drafts':
      caches       populated KV caches, slots [0, W) = [prompt | draft]
      seed_logits  (B, V) logits at the last accepted token (index P+n-1;
                   the last prompt token when n == 0) — the continuation's
                   first sampling distribution.
    """
    B, P = prompt.shape
    N = draft_tokens.shape[1]
    W = P + N
    didx = jnp.arange(N, dtype=jnp.int32)[None, :]
    draft_mask = didx < draft_len[:, None]

    full = jnp.concatenate([prompt, jnp.where(draft_mask, draft_tokens, 0)], axis=1)
    mask = jnp.concatenate([prompt_mask, draft_mask], axis=1)
    positions = positions_from_mask(mask)
    extras = {k: model_kwargs.get(k) for k in
              ("encoder_out", "encoder_positions")}
    caches = M.init_cache(cfg, B, W + N)
    if mesh is not None:
        from repro.distributed.mesh import constrain_caches
        caches = constrain_caches(cfg, caches, mesh)
    logits, caches = M.prefill(params, cfg, full, positions, caches, **extras)

    # same token-logprob extraction as engine.score (logits[t] -> token t+1)
    lp_next = logprobs_of(logits[:, :-1], full[:, 1:], temperature, top_p)
    lp = jnp.concatenate([jnp.zeros_like(lp_next[:, :1]), lp_next], axis=1)
    valid = mask & jnp.concatenate([jnp.zeros_like(mask[:, :1]), mask[:, :-1]],
                                   axis=1)
    lp_curr = jnp.where(valid, lp, 0.0)[:, P:]            # (B, N)

    u = _accept_uniforms(key, B, N)
    n = _spec_verify(mesh, lp_curr, draft_logprobs, u, draft_len,
                     log_lenience)

    seed_idx = P + n.astype(jnp.int32) - 1                # n==0 -> last prompt tok
    seed_logits = jnp.take_along_axis(
        logits, seed_idx[:, None, None], axis=1)[:, 0]

    total = jnp.maximum(draft_len.sum(), 1)
    return {"n": n, "lp_curr": lp_curr, "accept_rate": n.sum() / total,
            "caches": caches, "seed_logits": seed_logits}


# §14 recompile sentinel enrollment (obs/alerts.py): both verify entry
# points — the two-pass scorer and the fused one-pass admission program
from repro.obs.alerts import register_jit_entry  # noqa: E402

register_jit_entry("verify_drafts", verify_drafts)
register_jit_entry("verify_and_prefill", verify_and_prefill)
