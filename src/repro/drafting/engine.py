"""Host-driven drafted decode loops: the §9 counterparts of
``engine/generate.generate`` and ``resume_from_cache``.

The vanilla decode loop is one jit'd ``lax.while_loop``; drafting needs the
host in the loop (the n-gram proposal is a hash-map lookup), so these
functions run the same stages as their vanilla twins but step through the
jit'd ``drafting.step.draft_step`` macro-step, proposing between steps:

    prefill (jit)  ->  [propose (host) -> draft_step (jit)]*  ->  pack

Contracts mirrored from the vanilla loops:

* same output dict (``tokens``/``logprobs``/``length``/``n_generated``),
  plus a ``stats`` DraftStats;
* same greedy token stream: under temperature <= 0 acceptance is exactly
  "draft == argmax" and correction is argmax, so the emitted stream is the
  vanilla greedy stream whatever the proposals were (asserted in
  tests/drafting/);
* same per-token *marginal* distribution under temperature / top-p — the
  rejection-sampling guarantee (chi-squared-tested), though the PRNG
  draws divide differently so sampled streams are not bit-equal;
* caches end byte-equivalent over the live region (rejected slots are
  invalidated and overwritten), so SPEC-RL's next-epoch verification sees
  the same layout either way.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import DraftStats
from repro.engine.generate import GenerateConfig, positions_from_mask
from repro.engine.sampling import sample, split_key
from repro.models import model as M
from repro.models.config import ModelConfig

from .controller import DraftConfig, DraftController
from .ngram import NGramDraftSource


@functools.partial(jax.jit, static_argnames=("cfg", "gen", "extra", "mesh"))
def _prefill_seed(params, cfg: ModelConfig, gen: GenerateConfig, prompt,
                  prompt_mask, key, *, extra: int, mesh=None):
    """``generate``'s prefill stage with ``extra`` spare cache slots, plus
    the seed sample — the same key-split order as ``_decode_loop``."""
    B, P = prompt.shape
    positions = positions_from_mask(prompt_mask)
    caches = M.init_cache(cfg, B, P + gen.max_new_tokens + extra)
    if mesh is not None:
        from repro.distributed.mesh import constrain_caches
        caches = constrain_caches(cfg, caches, mesh)
    logits, caches = M.prefill(params, cfg, prompt, positions, caches)
    key, sub = split_key(key)
    tok0, lp0 = sample(sub, logits[:, -1], gen.temperature, gen.top_p)
    next_pos = prompt_mask.sum(axis=1).astype(jnp.int32)
    return {"caches": caches, "tok0": tok0, "lp0": lp0,
            "next_pos": next_pos, "key": key}


@functools.partial(jax.jit, static_argnames=("cfg", "gen", "extra", "mesh"))
def _pad_seed(params, cfg: ModelConfig, gen: GenerateConfig, caches,
              seed_logits, key, *, extra: int, mesh=None):
    """``resume_from_cache``'s entry: pad the compacted caches with draft
    headroom and seed-sample with the vanilla key-split order."""
    caches = M.pad_cache(cfg, caches, extra)
    if mesh is not None:
        from repro.distributed.mesh import constrain_caches
        caches = constrain_caches(cfg, caches, mesh)
    key, sub = split_key(key)
    tok0, lp0 = sample(sub, seed_logits, gen.temperature, gen.top_p)
    return {"caches": caches, "tok0": tok0, "lp0": lp0, "key": key}


class _DraftLoop:
    """Shared host loop: state vectors + propose/step/harvest plumbing."""

    def __init__(self, params, cfg: ModelConfig, gen: GenerateConfig,
                 draft: DraftConfig, caches, tok0, lp0, next_pos, key,
                 write_idx, initial_done, row_budget, contexts,
                 corpus, mesh):
        from .step import draft_step
        self._step = draft_step
        B = int(np.asarray(next_pos).shape[0])
        N = gen.max_new_tokens
        self.params, self.cfg, self.gen, self.mesh = params, cfg, gen, mesh
        self.K = draft.draft_k
        self.caches = caches
        self.cur_tok = tok0
        self.cur_lp = lp0
        self.key = key
        self.next_pos = jnp.asarray(next_pos, jnp.int32)
        self.write_idx = jnp.asarray(write_idx, jnp.int32)
        budget = jnp.full((B,), N, jnp.int32) if row_budget is None else \
            jnp.asarray(row_budget, jnp.int32)
        done0 = jnp.zeros((B,), bool) if initial_done is None else \
            jnp.asarray(initial_done)
        self.done = done0 | (budget <= 0)
        self.budget = budget
        self.count = jnp.zeros((B,), jnp.int32)
        self.source = NGramDraftSource(draft, B)
        self.controller = DraftController(draft, B)
        for b in range(B):
            self.source.reset(b, contexts[b],
                              corpus[b] if corpus is not None else None)
        self.acc_tok: List[List[np.ndarray]] = [[] for _ in range(B)]
        self.acc_lp: List[List[np.ndarray]] = [[] for _ in range(B)]
        self.stats = DraftStats()
        self.B, self.N = B, N
        # §14 provenance: when the caller bound ledger rows (spec_rollout's
        # one-pass continuation extends the rollout's own rows), append to
        # those; otherwise reserve fresh rows and lay each row's context
        # down as its prompt plane.  Host-side only — the jit'd step above
        # is untouched, so lowered HLO is identical ledger on/off.
        from repro.obs import get_ledger
        self.ledger = led = get_ledger()
        self._rows: List = [None] * B
        self._carry_bonus = np.zeros(B, bool)
        if led.enabled:
            bound = [led.bound_row(b) for b in range(B)]
            if all(r is not None for r in bound):
                self._rows = bound
            else:
                base = led.reserve(B)
                self._rows = [base + b for b in range(B)]
                for b in range(B):
                    led.begin_row(self._rows[b], len(contexts[b]))

    def run(self) -> Dict[str, jnp.ndarray]:
        # §11: the global tracer draws one span per draft macro-step on the
        # 'draft' lane (proposal + forward + harvest — the harvest's
        # np.asarray is the loop's existing host sync, so the end stamp
        # adds no new blocking); the acceptance time series rides the span
        # args.  Clock reads are guarded on tr.enabled — a NULL_TRACER run
        # takes none.
        from repro.obs import get_decision_log, get_registry, get_tracer
        from repro.obs.ledger import SOURCE_NGRAM, categorize_draft_block
        tr = get_tracer()
        reg = get_registry()
        led = self.ledger
        dec = get_decision_log()
        macro_step = 0
        while True:
            done_np = np.asarray(self.done)
            if done_np.all():
                break
            t0 = (tr.now() if tr.enabled else
                  time.perf_counter() if dec.enabled else 0.0)
            cur_np = np.asarray(self.cur_tok)
            dt = np.zeros((self.B, self.K), np.int32)
            dl = np.zeros((self.B,), np.int32)
            feats: Dict[int, Dict[str, float]] = {}
            if dec.enabled:
                cur_lp_np = np.asarray(self.cur_lp)
                pos_np = np.asarray(self.next_pos)
            for b in range(self.B):
                if done_np[b]:
                    continue
                k_b = self.controller.draft_len(b)
                d = self.source.propose(b, k_b, pending=int(cur_np[b]))
                dt[b, :len(d)] = d
                dl[b] = len(d)
                if dec.enabled:
                    # §14 decision features, captured pre-step (surprisal
                    # is -logp of the pending carry token — the host-side
                    # stand-in for next-token entropy; the fixed-batch
                    # loop has no queue or pool, so those columns are 0)
                    feats[b] = {
                        "surprisal": -float(cur_lp_np[b]),
                        "position": float(pos_np[b]),
                        "accept_ema": float(self.controller.rate[b]),
                        "draft_k": float(len(d)),
                        "draft_source": SOURCE_NGRAM,
                        "slot_age": float(macro_step),
                    }
            # compile the block at the power-of-two cover of the widest
            # live proposal — adaptive draft lengths narrow the forward
            # (drafting/step.py:block_width); acceptance draws stay at
            # u_width = draft_k so streams are bucket-invariant
            from .step import block_width
            K_step = block_width(int(dl.max()), self.K)
            out = self._step(
                self.params, self.cfg, self.gen, self.caches, self.cur_tok,
                self.cur_lp, self.done, self.count, self.budget,
                self.next_pos, self.write_idx, self.key,
                jnp.asarray(dt[:, :K_step]), jnp.asarray(dl), K=K_step,
                u_width=self.K, mesh=self.mesh)
            self.caches = out["caches"]
            for name in ("cur_tok", "cur_lp", "done", "count", "next_pos",
                         "write_idx"):
                setattr(self, name, out[name])
            self.key = out["keys"]
            toks = np.asarray(out["tokens"])
            lps = np.asarray(out["logprobs"])
            emitted = np.asarray(out["emitted"])
            accepted = np.asarray(out["accepted"])
            proposed = np.asarray(out["proposed"])
            t1 = (tr.now() if tr.enabled else
                  time.perf_counter() if dec.enabled else 0.0)
            for b in range(self.B):
                mb = int(emitted[b])
                if mb:
                    self.acc_tok[b].append(toks[b, :mb])
                    self.acc_lp[b].append(lps[b, :mb])
                    self.source.extend(b, toks[b, :mb])
                    if led.enabled:
                        for cat, nrun in categorize_draft_block(
                                mb, bool(self._carry_bonus[b])):
                            led.append(self._rows[b], cat, nrun)
                self._carry_bonus[b] = bool(
                    proposed[b] > 0 and accepted[b] == proposed[b])
                self.controller.update(b, int(proposed[b]), int(accepted[b]))
            if dec.enabled and feats:
                step_ms = (t1 - t0) * 1e3
                for b, f in feats.items():
                    prop, acc = int(proposed[b]), int(accepted[b])
                    mb = int(emitted[b])
                    dec.record(self._rows[b] if self._rows[b] is not None
                               else b, macro_step, f, {
                                   "proposed": prop, "accepted": acc,
                                   "bonus": 1.0 if (prop > 0 and acc == prop
                                                    and mb > acc) else 0.0,
                                   "emitted": mb, "step_ms": step_ms})
            # per-ROW forward counting: one batched forward serves `live`
            # rows, so tokens_per_forward is a per-row quantity with 1.0 as
            # the vanilla baseline (a live vanilla row emits exactly one
            # token per forward it participates in)
            n_prop, n_acc = int(proposed.sum()), int(accepted.sum())
            self.stats.add_step(forwards=int((~done_np).sum()),
                                proposed=n_prop, accepted=n_acc,
                                emitted=int(emitted.sum()),
                                draft_forwards=int((dl > 0).sum()))
            reg.observe("draft.proposed_per_step", n_prop)
            reg.observe("draft.accepted_per_step", n_acc)
            if tr.enabled:
                tr.complete("draft_step", "draft", t0, tr.now(), cat="draft",
                            step=macro_step, live=int((~done_np).sum()),
                            proposed=n_prop, accepted=n_acc,
                            emitted=int(emitted.sum()))
            macro_step += 1
        return self._pack(macro_step)

    def _pack(self, steps: int) -> Dict[str, jnp.ndarray]:
        tokens = np.full((self.B, self.N), self.gen.pad_id, np.int32)
        lps = np.zeros((self.B, self.N), np.float32)
        length = np.zeros((self.B,), np.int32)
        for b in range(self.B):
            row = np.concatenate(self.acc_tok[b]) if self.acc_tok[b] else \
                np.zeros(0, np.int32)
            lp_row = np.concatenate(self.acc_lp[b]) if self.acc_lp[b] else \
                np.zeros(0, np.float32)
            L = min(len(row), self.N)
            tokens[b, :L] = row[:L]
            lps[b, :L] = lp_row[:L]
            length[b] = L
        return {"tokens": jnp.asarray(tokens), "logprobs": jnp.asarray(lps),
                "length": jnp.asarray(length),
                "n_generated": jnp.asarray(length.sum()),
                "steps": steps, "stats": self.stats}


def drafted_generate(params, cfg: ModelConfig, gen: GenerateConfig, prompt,
                     prompt_mask, key, draft: DraftConfig, *,
                     corpus: Optional[Sequence[Sequence[np.ndarray]]] = None,
                     initial_done=None, row_budget=None, mesh=None
                     ) -> Dict[str, jnp.ndarray]:
    """``generate`` with the drafted decode loop (same output contract,
    plus ``stats``).  ``corpus[b]`` optionally holds row b's sibling /
    previous-rollout trajectories for the n-gram index."""
    assert M.supports_drafting(cfg), "drafting needs an attention-only trunk"
    B, P = prompt.shape
    pre = _prefill_seed(params, cfg, gen, jnp.asarray(prompt),
                        jnp.asarray(prompt_mask), key, extra=draft.draft_k,
                        mesh=mesh)
    mask_np = np.asarray(prompt_mask)
    prompt_np = np.asarray(prompt)
    contexts = [prompt_np[b][mask_np[b]] for b in range(B)]
    loop = _DraftLoop(params, cfg, gen, draft, pre["caches"], pre["tok0"],
                      pre["lp0"], pre["next_pos"], pre["key"],
                      np.full((B,), P, np.int32), initial_done, row_budget,
                      contexts, corpus, mesh)
    return loop.run()


def drafted_resume(params, cfg: ModelConfig, gen: GenerateConfig, caches,
                   seed_logits, next_pos, write_offset: int, key,
                   draft: DraftConfig, contexts: Sequence[Sequence[int]], *,
                   corpus: Optional[Sequence[Sequence[np.ndarray]]] = None,
                   initial_done=None, row_budget=None, mesh=None
                   ) -> Dict[str, jnp.ndarray]:
    """``resume_from_cache`` with the drafted decode loop — the one-pass
    SPEC-RL continuation drafts past the verified prefix (DESIGN.md §9).

    ``contexts[b]`` must hold row b's prompt ⊕ accepted-prefix tokens (the
    n-gram index needs the token values; the caches only hold K/V)."""
    assert M.supports_drafting(cfg), "drafting needs an attention-only trunk"
    B = seed_logits.shape[0]
    pre = _pad_seed(params, cfg, gen, caches, seed_logits, key,
                    extra=draft.draft_k, mesh=mesh)
    loop = _DraftLoop(params, cfg, gen, draft, pre["caches"], pre["tok0"],
                      pre["lp0"], next_pos, pre["key"],
                      np.full((B,), write_offset, np.int32), initial_done,
                      row_budget, contexts, corpus, mesh)
    return loop.run()
