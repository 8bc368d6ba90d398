"""SPEC-RL speculative rollout orchestrator (paper §3, Algorithm 1 + §3.2).

Per training step, for each prompt in the batch:

1. retrieve the cached previous rollout as a *draft* (cold start ⇒ empty),
2. verify all drafts in ONE packed forward of the current policy,
3. keep the verified prefix ``y_prev[:n]``,
4. resume generation for every row in ONE packed decode,
5. assemble ``y = y_prev[:n] ⊕ y_cont`` and refresh the cache immediately.

Continuation runs on one of two engine paths (DESIGN.md §3):

* **one-pass** (default for ``spec``/``delayed`` on attention trunks): the
  verification forward is a *prefilling* one (verify_and_prefill), its KV
  caches are compacted to the accepted region by the cache_gather kernel
  (model.realign_decode_cache), and decoding resumes straight from the
  compacted cache (engine.resume_from_cache).  Prompt ⊕ accepted prefix is
  forwarded exactly once per step — no second prefill.
* **two-pass** (fallback for recurrent trunks / ``random`` / ``full`` and
  the ``one_pass='off'`` escape hatch): score-then-re-prefill, where
  ``left_align`` packs prompt ⊕ prefix (the paper's padding trick) and
  ``generate`` prefills it again.  Sample-for-sample identical to one-pass
  under the same PRNG key (tested).

Variants (paper Table 2 / §4.3): ``spec`` (the method), ``random`` (uniform
rejection position, stale behaviour log-probs, no verification pass),
``delayed`` (drafts from two visits ago), ``full`` (ℓ→∞: reuse everything),
``off`` (vanilla RLVR).
"""
from __future__ import annotations

import functools
import math
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.drafting.controller import DraftConfig
from repro.engine.generate import (GenerateConfig, generate,
                                   resume_from_cache)
from repro.engine.sampling import split_key
from repro.models import model as M
from repro.models.config import ModelConfig

from .cache import RolloutCache
from .verify import verify_and_prefill, verify_drafts

VARIANTS = ("off", "spec", "random", "delayed", "full")


@dataclass(frozen=True)
class SpecConfig:
    variant: str = "spec"
    lenience: float = math.e ** 0.5     # paper default for GRPO
    cache_history: int = 4
    verify_impl: str = "auto"           # kernels.spec_verify impl selector
    one_pass: str = "auto"              # 'auto' | 'on' | 'off' — fused
                                        # verify→compact→resume engine path
    compact_impl: str = "auto"          # kernels.cache_gather impl selector
    backfill: str = "none"              # 'none' | 'slots' — continuous-
                                        # batching rollout (DESIGN.md §6):
                                        # finished rows immediately pick up
                                        # pending prompts via the serving
                                        # slot scheduler
    backfill_slots: int = 0             # decode-batch size for 'slots'
                                        # (0 -> half the prompt batch)
    cache_max_prompts: Optional[int] = None  # RolloutCache LRU bound
    draft: DraftConfig = DraftConfig()  # §9 continuation draft engine:
                                        # n-gram/sibling drafts + multi-token
                                        # verify inside the decode loop
                                        # (kind='off' = vanilla decoding)

    @property
    def cache_lag(self) -> int:
        return 2 if self.variant == "delayed" else 1

    @property
    def log_lenience(self) -> float:
        return math.log(self.lenience) if math.isfinite(self.lenience) else 1e9


@dataclass
class RolloutBatch:
    """Uniform output consumed by the RL trainer, whatever the variant."""
    prompt: np.ndarray            # (B, P) left-padded
    prompt_mask: np.ndarray       # (B, P)
    response: np.ndarray          # (B, N) right-padded
    response_mask: np.ndarray     # (B, N)
    behaviour_logprobs: np.ndarray  # (B, N) log-probs under the behaviour dist
    length: np.ndarray            # (B,)
    metrics: Dict[str, float] = field(default_factory=dict)


@functools.partial(jax.jit, static_argnames=("impl",))
def left_align(tokens, mask, impl: str = "gather"):
    """Shift each row so its last valid token sits in the last column.

    Requires the columns after the last valid one to be padding (true for
    [left-padded prompt | right-padded prefix] layouts).

    impl='gather' (default) lowers to ONE take_along_axis gather with
    modular source indices — the per-row dynamic roll lowers poorly on
    TPU.  impl='roll' is the legacy vmap'd per-row jnp.roll, kept as the
    fallback used by the non-spec variants (random / full ablations) and
    as the oracle for the gather path (bit-identical by construction).
    """
    W = tokens.shape[1]
    idx = jnp.arange(W, dtype=jnp.int32)[None, :]
    end = jnp.max(jnp.where(mask, idx + 1, 0), axis=1)      # (B,)
    shift = W - end
    if impl == "roll":
        roll = jax.vmap(lambda t, s: jnp.roll(t, s, axis=0))
        return roll(tokens, shift), roll(mask, shift)
    src = (idx - shift[:, None]) % W
    return (jnp.take_along_axis(tokens, src, axis=1),
            jnp.take_along_axis(mask, src, axis=1))


@functools.partial(jax.jit, static_argnames=("pad_id",))
def assemble(draft_tokens, prefix_lp, n, cont_tokens, cont_lp, cont_len,
             *, pad_id: int = 0):
    """y = draft[:n] ⊕ continuation, right-padded to N columns.

    prefix_lp: (B, N) behaviour log-probs to use for the reused prefix.
    Returns (tokens, lp, mask, length).
    """
    B, N = draft_tokens.shape
    j = jnp.arange(N, dtype=jnp.int32)[None, :]
    in_prefix = j < n[:, None]
    total = n + cont_len
    in_resp = j < total[:, None]

    gather = jnp.clip(j - n[:, None], 0, N - 1)
    cont_tok_shift = jnp.take_along_axis(cont_tokens, gather, axis=1)
    cont_lp_shift = jnp.take_along_axis(cont_lp, gather, axis=1)

    tokens = jnp.where(in_prefix, draft_tokens,
                       jnp.where(in_resp, cont_tok_shift, pad_id))
    lp = jnp.where(in_prefix, prefix_lp, jnp.where(in_resp, cont_lp_shift, 0.0))
    return tokens, lp, in_resp, total


def _vanilla(params, cfg, gen, prompts, prompt_mask, key, model_kwargs,
             mesh=None):
    out = generate(params, cfg, gen, prompts, prompt_mask, key, mesh=mesh,
                   **model_kwargs)
    return out


def use_drafting(cfg: ModelConfig, spec: SpecConfig, model_kwargs) -> bool:
    """Whether the §9 drafted decode loop replaces the vanilla while_loop.

    Needs rewindable per-slot KV state (attention-only trunk, no modality
    extras — model.supports_drafting); recurrent trunks and the random/full
    ablations (whose continuations ride the legacy two-pass path) decode
    vanilla."""
    return spec.draft.enabled and M.supports_drafting(cfg, model_kwargs)


def _emit_rollout_obs(spec, metrics, stages, n=None):
    """§11 per-epoch rollout telemetry: registry histograms/counters for
    the stage durations and the paper's headline diagnostics (reuse length,
    acceptance, lenience).  The stage durations are the perf_counter stamps
    the metrics dict already took at existing block_until_ready boundaries,
    so with an idle registry this adds no syncs and no clock reads beyond
    a few dict ops.  The stage spans are scoped around the stages."""
    from repro.obs import get_registry
    reg = get_registry()
    step = int(metrics.get("step", 0))
    for name, dur in stages:
        reg.observe(f"rollout.{name}_s", dur)
    reg.observe("rollout.accept_rate", metrics.get("accept_rate", 0.0))
    reg.set("rollout.lenience", float(spec.lenience)
            if math.isfinite(spec.lenience) else 0.0)
    reg.set("rollout.step", float(step), agg="max")
    reg.inc("rollout.generated_tokens", metrics.get("n_generated", 0))
    reg.inc("rollout.reused_tokens", metrics.get("n_reused", 0))
    if n is not None:
        for v in np.asarray(n).reshape(-1):
            reg.observe("rollout.reuse_len", float(v))


def _held_metric(held) -> Dict[str, float]:
    """``moe_held_tokens``: the decode loop's routed assignments that land
    on held experts (MoE trunks; nothing for others)."""
    return {} if held is None else {"moe_held_tokens": float(held)}


def _draft_metrics(stats=None) -> Dict[str, float]:
    """Rollout-metric view of a DraftStats (zeros when drafting is off).

    ``accept_rate`` is already taken by SPEC-RL prefix verification, so the
    draft-engine ratios ride a ``draft_`` prefix; ``tokens_per_forward`` is
    the headline decode-efficiency number (1.0 = vanilla)."""
    from repro.core.metrics import DraftStats
    st = stats or DraftStats()
    return {"draft_accept_rate": st.accept_rate,
            "draft_mean_len": st.mean_draft_len,
            "tokens_per_forward": st.tokens_per_forward if st.forwards
            else 1.0,
            "decode_forwards": float(st.forwards)}


def _ledger_rows(led, B: int, prompt_mask):
    """Reserve + begin one §14 provenance row per batch row.

    Host-side only: reads the prompt mask (already materialised by every
    caller path) and touches no device code, so the lowered programs are
    byte-identical ledger on/off.  Returns (row_ids, prompt_lens)."""
    p_np = np.asarray(prompt_mask).sum(axis=1).astype(np.int64)
    base = led.reserve(B)
    rows = [base + b for b in range(B)]
    for b in range(B):
        led.begin_row(rows[b], int(p_np[b]))
    return rows, p_np


def use_one_pass(cfg: ModelConfig, spec: SpecConfig, model_kwargs) -> bool:
    """Whether the fused verify→compact→resume path applies.

    Needs per-slot KV state in every layer (attention-only trunk) and no
    vision prefix (whose extra cache slots the compactor does not model).
    """
    if spec.variant not in ("spec", "delayed") or spec.one_pass == "off":
        return False
    ok = (M.supports_cache_realign(cfg)
          and model_kwargs.get("prefix_embeds") is None)
    if spec.one_pass == "on" and not ok:
        raise ValueError("one_pass='on' requires an attention-only trunk "
                         "and no prefix_embeds")
    return ok


def rollout(params, cfg: ModelConfig, gen: GenerateConfig, spec: SpecConfig,
            prompts, prompt_mask, prompt_ids: Sequence[int],
            cache: Optional[RolloutCache], key, step: int, mesh=None,
            **model_kwargs) -> RolloutBatch:
    """One rollout step for a prompt batch.  Host-level: the cache is host
    memory; verification / compaction / generation / assembly are jit'd
    device calls.

    ``key`` may be (2,) — the classic batched PRNG stream — or (B, 2)
    per-request keys, which make every row's tokens independent of batch
    grouping (the contract the slot-backfill mode relies on).  With
    ``spec.backfill == 'slots'`` the whole step is drained through the
    serving slot scheduler instead of the fixed decode batch: rows that
    finish early immediately pick up pending prompts (DESIGN.md §6).

    ``mesh``: optional live Mesh (DESIGN.md §8).  Batch rows are placed over
    the data axes, params are expected pre-sharded by the caller, and every
    device stage — verify, compact, resume/generate — runs the same SPMD
    program, so the output is token-identical to the single-device path.

    Observability (DESIGN.md §11): the whole call is the ``rollout.rollout``
    span and ``metrics["rollout_time"]``; its stages are child spans
    (``cache_get``, ``verify``, ``compact``, ``decode`` or ``generate``,
    ``assembly``, ``cache_put``, ``to_host``), each device stage ending at
    a sync on its outputs.  ``metrics["decode_steps"]`` is the decode
    loop's trip count, and on MoE trunks ``metrics["moe_held_tokens"]`` its
    routed assignments on held experts, from the same transfer.
    """
    assert spec.variant in VARIANTS, spec.variant
    from repro.obs import get_registry, get_tracer
    t0 = time.perf_counter()
    with get_tracer().span("rollout", "rollout", cat="rollout", step=step):
        if spec.backfill == "slots":
            from repro.serving.rl_adapter import rollout_via_slots
            rb = rollout_via_slots(params, cfg, gen, spec, prompts,
                                   prompt_mask, prompt_ids, cache, key, step,
                                   mesh=mesh, **model_kwargs)
        else:
            assert spec.backfill == "none", spec.backfill
            rb = _rollout_fixed(params, cfg, gen, spec, prompts, prompt_mask,
                                prompt_ids, cache, key, step, mesh,
                                model_kwargs)
        rollout_time = time.perf_counter() - t0
    rb.metrics["rollout_time"] = rollout_time
    reg = get_registry()
    reg.observe("rollout.step_s", rollout_time)
    reg.inc("rollout.decode_steps", rb.metrics["decode_steps"])
    return rb


def _to_host(prompts, prompt_mask, resp, resp_mask, lp, length, metrics):
    from repro.obs import get_tracer
    with get_tracer().span("to_host", "rollout", cat="rollout"):
        return RolloutBatch(
            prompt=np.asarray(prompts), prompt_mask=np.asarray(prompt_mask),
            response=np.asarray(resp), response_mask=np.asarray(resp_mask),
            behaviour_logprobs=np.asarray(lp), length=np.asarray(length),
            metrics=metrics)


def _rollout_fixed(params, cfg, gen, spec, prompts, prompt_mask, prompt_ids,
                   cache, key, step, mesh, model_kwargs) -> RolloutBatch:
    """``rollout`` on the fixed decode batch (every ``backfill='none'``
    path): fresh ``generate``, or verify → compact → resume / re-prefill."""
    from repro.obs import get_ledger, get_tracer
    from repro.obs.ledger import FRESH, REUSED_PREFIX
    tr = get_tracer()
    if mesh is not None:
        from repro.distributed.mesh import shard_batch
        prompts, prompt_mask = shard_batch(mesh, (jnp.asarray(prompts),
                                                  jnp.asarray(prompt_mask)))
        if jnp.ndim(key) == 2:
            key = shard_batch(mesh, key)
    B, P = prompts.shape
    N = gen.max_new_tokens
    metrics: Dict[str, float] = {"step": step}
    led = get_ledger()

    use_cache = spec.variant != "off" and cache is not None
    with tr.span("cache_get", "rollout", cat="rollout"):
        drafts = cache.batch_get(prompt_ids, N, spec.cache_lag) \
            if use_cache else None
        have_drafts = use_cache and int(drafts["draft_len"].sum()) > 0

    drafting = use_drafting(cfg, spec, model_kwargs)

    if not have_drafts:
        key, sub = split_key(key)
        rows = p_np = None
        if led.enabled:
            rows, p_np = _ledger_rows(led, B, prompt_mask)
        with tr.span("generate", "rollout", cat="rollout"):
            tg0 = time.perf_counter()
            if drafting:
                from repro.drafting import drafted_generate
                corpus = cache.batch_siblings(prompt_ids, spec.cache_lag) \
                    if use_cache else None
                # bind the rollout's rows so _DraftLoop's per-macro-step
                # provenance appends land on them instead of fresh rows
                if rows is not None:
                    led.bind(rows)
                try:
                    out = drafted_generate(params, cfg, gen, prompts,
                                           prompt_mask, sub, spec.draft,
                                           corpus=corpus,
                                           verify_impl=spec.verify_impl,
                                           mesh=mesh)
                finally:
                    if rows is not None:
                        led.unbind()
            else:
                out = _vanilla(params, cfg, gen, prompts, prompt_mask, sub,
                               model_kwargs, mesh=mesh)
            resp, lp, length = out["tokens"], out["logprobs"], out["length"]
            # dispatched before the sync, so its launch hides behind the loop
            resp_mask = jnp.arange(N)[None, :] < length[:, None]
            # the loop's scalar outputs, read in one transfer: the sync that
            # ends the stage (a program's outputs are ready together)
            n_generated, decode_steps, held = jax.device_get(
                (out["n_generated"], out["steps"],
                 out.get("moe_held_tokens")))
            decode_time = time.perf_counter() - tg0
        metrics.update(_held_metric(held))
        metrics.update(
            n_generated=int(n_generated),
            decode_steps=int(decode_steps), n_reused=0,
            verified_prefix_mean=0.0, full_reuse_ratio=0.0,
            accept_rate=0.0, draft_coverage=0.0,
            verify_time=0.0, assembly_time=0.0, compact_time=0.0,
            decode_time=decode_time, one_pass=0.0, prefill_passes=1.0,
            **_draft_metrics(out.get("stats")))
        _emit_rollout_obs(spec, metrics, [("generate", decode_time)])
        _update_cache(cache, prompt_ids, resp, lp, length, step, gen.eos_id)
        if rows is not None:
            len_np = np.asarray(length)
            for b in range(B):
                if not drafting:   # drafted rows were filled by _DraftLoop
                    led.append(rows[b], FRESH, int(len_np[b]))
                led.finalize(rows[b], int(p_np[b]) + int(len_np[b]))
        return _to_host(prompts, prompt_mask, resp, resp_mask, lp, length,
                        metrics)

    draft_tokens = jnp.asarray(drafts["draft_tokens"])
    draft_lp = jnp.asarray(drafts["draft_logprobs"])
    draft_len = jnp.asarray(drafts["draft_len"])
    draft_eos = jnp.asarray(drafts["draft_eos"])
    if mesh is not None:
        from repro.distributed.mesh import shard_batch
        draft_tokens, draft_lp, draft_len, draft_eos = shard_batch(
            mesh, (draft_tokens, draft_lp, draft_len, draft_eos))
    one_pass = use_one_pass(cfg, spec, model_kwargs)
    led_rows = led_p = None
    if led.enabled:
        led_rows, led_p = _ledger_rows(led, B, prompt_mask)

    if one_pass:
        # ---- fused path: ONE forward over prompt ⊕ draft -----------------
        with tr.span("verify", "rollout", cat="rollout"):
            tv0 = time.perf_counter()
            key, sub = split_key(key)
            ver = verify_and_prefill(params, cfg, prompts, prompt_mask,
                                     draft_tokens, draft_lp, draft_len, sub,
                                     spec.log_lenience,
                                     temperature=gen.temperature,
                                     top_p=gen.top_p, impl=spec.verify_impl,
                                     mesh=mesh, **model_kwargs)
            n = ver["n"]
            prefix_lp = ver["lp_curr"]
            accept_rate = float(ver["accept_rate"])
            jax.block_until_ready(n)
            verify_time = time.perf_counter() - tv0

        # compact the caches to [prompt | draft[:n]], left-aligned at W
        W = P + N
        with tr.span("compact", "rollout", cat="rollout"):
            tc0 = time.perf_counter()
            p_len = jnp.sum(prompt_mask, axis=1).astype(jnp.int32)
            caches = M.realign_decode_cache(cfg, ver["caches"],
                                            (N - n).astype(jnp.int32),
                                            p_len + n, W,
                                            impl=spec.compact_impl, mesh=mesh)
            # the decode stage's inputs, dispatched before the sync so their
            # launch hides behind the rolls
            full_reuse = (n == draft_len) & draft_eos
            key, sub = split_key(key)
            jax.block_until_ready(caches)       # every buffer's roll
            compact_time = time.perf_counter() - tc0

        # resume decoding from the compacted cache — zero redundant prefill
        with tr.span("decode", "rollout", cat="rollout"):
            td0 = time.perf_counter()
            if drafting:
                # §9: draft the continuation too — the n-gram index is
                # seeded with prompt ⊕ accepted prefix and the sibling
                # corpus, so the decode loop keeps speculating past the
                # verified prefix
                from repro.drafting import drafted_resume
                n_np = np.asarray(n)
                mask_np = np.asarray(prompt_mask)
                prompts_np = np.asarray(prompts)
                dt_np = np.asarray(draft_tokens)
                contexts = [np.concatenate([prompts_np[b][mask_np[b]],
                                            dt_np[b, :int(n_np[b])]])
                            for b in range(B)]
                corpus = cache.batch_siblings(prompt_ids, spec.cache_lag)
                # §14: the verified prefix is reused provenance; bind the
                # rows so the drafted continuation extends them in place
                if led_rows is not None:
                    for b in range(B):
                        led.append(led_rows[b], REUSED_PREFIX, int(n_np[b]))
                    led.bind(led_rows)
                try:
                    cont = drafted_resume(params, cfg, gen, caches,
                                          ver["seed_logits"], p_len + n, W,
                                          sub, spec.draft, contexts,
                                          corpus=corpus,
                                          initial_done=full_reuse,
                                          row_budget=N - n,
                                          verify_impl=spec.verify_impl,
                                          mesh=mesh)
                finally:
                    if led_rows is not None:
                        led.unbind()
            else:
                cont = resume_from_cache(params, cfg, gen, caches,
                                         ver["seed_logits"], p_len + n, W,
                                         sub, initial_done=full_reuse,
                                         row_budget=N - n, mesh=mesh,
                                         **model_kwargs)
            jax.block_until_ready(cont["tokens"])
            decode_time = time.perf_counter() - td0
        prefill_passes = 1.0
    else:
        # ---- two-pass path: rejection positions then re-prefill ----------
        with tr.span("verify", "rollout", cat="rollout"):
            tv0 = time.perf_counter()
            if spec.variant in ("spec", "delayed"):
                key, sub = split_key(key)
                ver = verify_drafts(params, cfg, prompts, prompt_mask,
                                    draft_tokens, draft_lp, draft_len, sub,
                                    spec.log_lenience,
                                    temperature=gen.temperature,
                                    top_p=gen.top_p, impl=spec.verify_impl,
                                    mesh=mesh, **model_kwargs)
                n = ver["n"]
                prefix_lp = ver["lp_curr"]      # current-policy probs (exact)
                accept_rate = float(ver["accept_rate"])
                prefill_passes = 2.0            # score fwd + re-prefill
            elif spec.variant == "random":
                key, sub = split_key(key)
                frac = (jax.vmap(lambda k: jax.random.uniform(k))(sub)
                        if jnp.ndim(sub) == 2
                        else jax.random.uniform(sub, (B,)))
                n = jnp.floor(frac * (draft_len + 1)).astype(jnp.int32)
                n = jnp.minimum(n, draft_len)
                prefix_lp = draft_lp            # stale behaviour probs
                accept_rate = float(jnp.where(
                    draft_len.sum() > 0,
                    n.sum() / jnp.maximum(draft_len.sum(), 1), 0.0))
                prefill_passes = 1.0
            else:  # full
                n = draft_len
                prefix_lp = draft_lp
                accept_rate = 1.0
                prefill_passes = 1.0
            jax.block_until_ready(n)
            verify_time = time.perf_counter() - tv0

        full_reuse = (n == draft_len) & draft_eos
        with tr.span("compact", "rollout", cat="rollout"):
            tc0 = time.perf_counter()
            j = jnp.arange(N, dtype=jnp.int32)[None, :]
            prefix_mask = j < n[:, None]
            combined = jnp.concatenate(
                [prompts, jnp.where(prefix_mask, draft_tokens, gen.pad_id)],
                axis=1)
            combined_mask = jnp.concatenate([prompt_mask, prefix_mask],
                                            axis=1)
            align_impl = "gather" if spec.variant in ("spec", "delayed") \
                else "roll"
            aligned_tokens, aligned_mask = left_align(combined, combined_mask,
                                                      impl=align_impl)
            jax.block_until_ready(aligned_tokens)
            compact_time = time.perf_counter() - tc0

        with tr.span("decode", "rollout", cat="rollout"):
            td0 = time.perf_counter()
            key, sub = split_key(key)
            cont = generate(params, cfg, gen, aligned_tokens, aligned_mask,
                            sub, initial_done=full_reuse, row_budget=N - n,
                            mesh=mesh, **model_kwargs)
            jax.block_until_ready(cont["tokens"])
            decode_time = time.perf_counter() - td0

    # ---- assembly ----------------------------------------------------------
    with tr.span("assembly", "rollout", cat="rollout"):
        ta0 = time.perf_counter()
        resp, lp, resp_mask, length = assemble(
            draft_tokens, prefix_lp, n, cont["tokens"], cont["logprobs"],
            cont["length"], pad_id=gen.pad_id)
        jax.block_until_ready(resp)
        assembly_time = time.perf_counter() - ta0

    _update_cache(cache, prompt_ids, resp, lp, length, step, gen.eos_id)

    if led_rows is not None:
        drafted_cont = one_pass and drafting
        n_fin = np.asarray(n)
        len_fin = np.asarray(length)
        for b in range(B):
            if not drafted_cont:   # drafted rows were extended by _DraftLoop
                led.append(led_rows[b], REUSED_PREFIX, int(n_fin[b]))
                led.append(led_rows[b], FRESH,
                           int(len_fin[b]) - int(n_fin[b]))
            led.finalize(led_rows[b], int(led_p[b]) + int(len_fin[b]))

    n_generated, decode_steps, held = jax.device_get(    # one transfer
        (cont["n_generated"], cont["steps"], cont.get("moe_held_tokens")))
    metrics.update(_held_metric(held))
    metrics.update(
        n_generated=int(n_generated),
        decode_steps=int(decode_steps),
        n_reused=int(n.sum()),
        verified_prefix_mean=float(n.mean()),
        full_reuse_ratio=float(full_reuse.mean()),
        accept_rate=accept_rate,
        draft_coverage=float((draft_len > 0).mean()),
        verify_time=verify_time, assembly_time=assembly_time,
        compact_time=compact_time, decode_time=decode_time,
        one_pass=float(one_pass), prefill_passes=prefill_passes,
        **_draft_metrics(cont.get("stats") if isinstance(cont, dict)
                         else None))
    _emit_rollout_obs(spec, metrics,
                      [("verify", verify_time), ("compact", compact_time),
                       ("decode", decode_time), ("assembly", assembly_time)],
                      n=np.asarray(n))
    return _to_host(prompts, prompt_mask, resp, resp_mask, lp, length,
                    metrics)


def _update_cache(cache: Optional[RolloutCache], prompt_ids, resp, lp, length,
                  step, eos_id):
    if cache is None:
        return
    from repro.obs import get_tracer
    with get_tracer().span("cache_put", "rollout", cat="rollout"):
        cache.batch_put(prompt_ids, np.asarray(resp), np.asarray(lp),
                        np.asarray(length), step, eos_id)
