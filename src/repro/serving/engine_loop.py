"""Persistent continuous-batching decode loop over slot-replaced caches.

The engine keeps ONE decode batch of ``num_slots`` rows alive — over dense
``(B, Hkv, S, D)`` cache slabs by default (in-place slot replacement, §6),
or over a paged block pool when built as the ``PagedSlotEngine`` subclass
(serving/paged_engine.py, DESIGN.md §13).  Whenever a row emits
EOS or exhausts its per-slot budget, the next queued request is prefilled —
optionally through ``verify_and_prefill`` so a cached SPEC-RL draft becomes
its speculative prefix — and written into the freed slot by the
``cache_slot_write`` batched-scatter kernel.  No other row notices: the
decode batch never drains to its slowest member.

Three jit'd device programs, all statically shaped:

* ``_admit_vanilla``  — prefill a padded admission group + seed sample;
* ``_admit_spec``     — fused verify+prefill over [prompt | draft], compact
  to the accepted prefix (cache_gather), seed sample at the last accepted
  token — speculative-prefix admission;
* ``_decode_chunk``   — ``chunk_steps`` decode steps for all B slots with
  per-row write offsets (each slot sits at its own depth), per-row PRNG
  streams and per-row budgets.  Its body is term-for-term the body of
  ``engine/generate._decode_loop``, which is what makes slot-scheduled
  output token-identical to fixed-batch ``generate`` (tested).

Host side: numpy state vectors + the SlotScheduler; admission groups are
padded to ``num_slots`` rows by duplicating a real admitted row (duplicate
slot writes carry identical bytes), so every jit sees one shape.

Fault tolerance (DESIGN.md §10): the engine never lets one bad row take the
batch down.  A non-finite-logit guard inside ``_decode_chunk`` quarantines
the offending row in-chunk (its garbage token is never stored; every other
row decodes on); per-request deadlines bound how long a straggler may hold
a slot; a reclaimed request retries through speculative-prefix admission —
its already-generated tokens become the retry's draft and are *verified*,
not regenerated; draft-source exceptions disable drafting for the row,
never crash the server; repeated quarantines walk the decode-impl ladder
(pallas → blocked → naive).  All of it is counted in ``stats()``, injected
deterministically by a ``FaultPlan`` (serving/faults.py), and the whole
engine state round-trips through ``state_dict``/``load_state_dict`` for
exact kill-and-resume (checkpoint/io.save_server_state).
"""
from __future__ import annotations

import functools
import math
import time
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backoff import BackoffConfig
from repro.core.metrics import FaultStats
from repro.core.verify import verify_and_prefill
from repro.obs import (MetricsRegistry, get_decision_log, get_ledger,
                       get_tracer)
from repro.obs.alerts import (record_compile_gauges, record_device_memory,
                              register_jit_entry)
from repro.obs.ledger import (FRESH, PROMPT, REUSED_PREFIX, SOURCE_NGRAM,
                              categorize_draft_block)
from repro.engine.generate import GenerateConfig, positions_from_mask
from repro.engine.sampling import sample, split_key
from repro.models import model as M
from repro.models.config import ModelConfig

from .faults import EngineKilled, FaultPlan
from .request import (DECODING, FINISH_BUDGET, FINISH_EOS, FINISH_FULL_REUSE,
                      FINISH_QUARANTINE, FINISH_SHED, FINISH_TIMEOUT,
                      Request, Response)
from .scheduler import SlotScheduler

# §10 graceful-degradation ladder for the decode-attention implementation:
# a row that keeps producing non-finite logits steps the engine down one
# rung (recompile on fault — the clean path never pays for it)
_IMPL_LADDER = {"pallas": "blocked", "interpret": "blocked",
                "auto": "blocked", "blocked": "naive", "naive": None}
_IMPL_NAMES = ("auto", "naive", "blocked", "pallas", "interpret")


@functools.partial(jax.jit, static_argnames=("cfg", "gen", "mesh"))
def _admit_vanilla(params, cfg: ModelConfig, gen: GenerateConfig, prompts,
                   mask, keys, mesh=None):
    """Prefill an admission group; mirrors ``generate`` up to the seed token.

    prompts: (R, P) left-padded; keys: (R, 2) per-request decode keys.
    Returns caches sized P + N per row (the exact layout fixed-batch
    ``generate`` builds), the seed token/logprob and the carry keys.
    """
    R, P = prompts.shape
    caches = M.init_cache(cfg, R, P + gen.max_new_tokens)
    if mesh is not None:
        from repro.distributed.mesh import constrain_caches
        caches = constrain_caches(cfg, caches, mesh, batch=False)
    logits, caches = M.prefill(params, cfg, prompts, positions_from_mask(mask),
                               caches)
    keys, sub = split_key(keys)
    tok0, lp0 = sample(sub, logits[:, -1], gen.temperature, gen.top_p)
    # seed_logits ride along for the paged engine's GRPO prompt sharing
    # (§13): a follower re-samples from its leader's prefill logits with its
    # own key instead of re-running the identical prefill
    return {"caches": caches, "tok0": tok0, "lp0": lp0,
            "next_pos": mask.sum(axis=1).astype(jnp.int32), "keys": keys,
            "seed_logits": logits[:, -1]}


@functools.partial(jax.jit, static_argnames=("cfg", "gen", "mesh"))
def _admit_spec(params, cfg: ModelConfig, gen: GenerateConfig, prompts, mask,
                draft_tokens, draft_lp, draft_len, draft_eos, verify_keys,
                decode_keys, log_lenience, *, mesh=None):
    """Speculative-prefix admission: one forward over [prompt | draft].

    Identical device program to the fixed-batch one-pass rollout path
    (verify_and_prefill → realign_decode_cache → seed sample), so a request
    admitted here continues from the same compacted cache, seed logits and
    PRNG stream as ``rollout`` would give it.
    """
    R, P = prompts.shape
    N = draft_tokens.shape[1]
    W = P + N
    ver = verify_and_prefill(params, cfg, prompts, mask, draft_tokens,
                             draft_lp, draft_len, verify_keys, log_lenience,
                             temperature=gen.temperature, top_p=gen.top_p,
                             mesh=mesh)
    n = ver["n"]
    p_len = mask.sum(axis=1).astype(jnp.int32)
    caches = M.realign_decode_cache(cfg, ver["caches"],
                                    (N - n).astype(jnp.int32), p_len + n, W,
                                    mesh=mesh)
    full_reuse = (n == draft_len) & draft_eos
    keys, sub = split_key(decode_keys)
    tok0, lp0 = sample(sub, ver["seed_logits"], gen.temperature, gen.top_p)
    return {"caches": caches, "tok0": tok0, "lp0": lp0, "n": n,
            "lp_curr": ver["lp_curr"], "full_reuse": full_reuse,
            "next_pos": p_len + n, "keys": keys}


@functools.partial(jax.jit, static_argnames=("cfg", "pad_src", "mesh"))
def _write_slots(cfg: ModelConfig, dst_caches, src_caches, slots, *,
                 pad_src: int = 0, mesh=None):
    # drafted engines keep draft_k spare slots per row (§9 block headroom);
    # admission caches are padded to the persistent width before the scatter
    if pad_src:
        src_caches = M.pad_cache(cfg, src_caches, pad_src)
    return M.write_cache_slots(cfg, dst_caches, src_caches, slots, mesh=mesh)


@functools.partial(jax.jit, static_argnames=("cfg", "gen", "steps", "mesh"))
def _decode_chunk(params, cfg: ModelConfig, gen: GenerateConfig, caches,
                  cur_tok, cur_lp, done, count, budget, next_pos, write_idx,
                  keys, nan_inject, *, steps: int, mesh=None):
    """``steps`` decode steps over all slots; per-row write offsets/streams.

    Term-for-term the body of ``engine/generate._decode_loop`` (store →
    count/done update → decode_step → split → sample), except the cache
    write lands at the per-row ``write_idx`` instead of a batch-wide offset
    and the loop never stops early — idle/done rows keep stepping with
    position −1 (position-masked attention ignores those writes, and the
    slot is fully rewritten at its next admission).

    §10 non-finite guard: a row whose logits go NaN/inf is *quarantined*
    in-chunk — its garbage sample is forced onto safe (uniform) logits and
    never stored, because quarantine sets ``done`` before the next store.
    Every other row decodes on undisturbed.  ``nan_inject`` (B,) is the
    fault-injection hook: the step index within this chunk at which a row's
    logits are deliberately corrupted, −1 (the clean-path constant) never.
    Both the injection and the guard are ``where``-selects over the same
    traced program, so a clean run is bit-identical to the pre-guard loop.
    """
    def body(carry, step_i):
        caches, cur_tok, cur_lp, done, count, next_pos, write_idx, keys, \
            quar = carry
        tok_store = jnp.where(done, gen.pad_id, cur_tok)
        lp_store = jnp.where(done, 0.0, cur_lp)
        count = count + (~done).astype(jnp.int32)
        done_next = done | (cur_tok == gen.eos_id) | (count >= budget)
        # per-row live extents: each slot sits at its own decode depth, so
        # the flash-decode kernel early-exits per row at write_idx + 1 and
        # skips the dead left padding below write_idx - next_pos (the
        # admitted context is contiguous — prefill or compacted layout)
        logits, caches = M.decode_step(
            params, cfg, tok_store[:, None],
            jnp.where(done[:, None], -1, next_pos[:, None]),
            caches, write_idx, kv_length=write_idx + 1,
            kv_start=write_idx - next_pos, mesh=mesh)
        lg = logits[:, 0]
        lg = jnp.where((nan_inject == step_i)[:, None], jnp.nan, lg)
        bad = ~jnp.all(jnp.isfinite(lg), axis=-1)
        newly = bad & ~done_next        # rows finishing anyway aren't pulled
        quar = quar | newly
        done_next = done_next | newly
        lg = jnp.where(bad[:, None], 0.0, lg)   # sample something finite;
        keys, sub = split_key(keys)             # done_next gates its store
        nxt, nlp = sample(sub, lg, gen.temperature, gen.top_p)
        carry = (caches, nxt, nlp, done_next, count, next_pos + 1,
                 write_idx + 1, keys, quar)
        return carry, (tok_store, lp_store)

    init = (caches, cur_tok, cur_lp, done, count, next_pos, write_idx, keys,
            jnp.zeros_like(done))
    carry, (toks, lps) = jax.lax.scan(body, init, jnp.arange(steps))
    caches, cur_tok, cur_lp, done, count, next_pos, write_idx, keys, \
        quar = carry
    return {"caches": caches, "cur_tok": cur_tok, "cur_lp": cur_lp,
            "done": done, "count": count, "next_pos": next_pos,
            "write_idx": write_idx, "keys": keys, "quarantined": quar,
            "tokens": toks.T, "logprobs": lps.T}      # (B, steps)


# §14 recompile sentinel: these module-level jit wrappers are the engine's
# device programs — their cache sizes are the per-entry compile counts the
# `recompile_steady_state` alert rule watches (obs/alerts.py)
register_jit_entry("admit_vanilla", _admit_vanilla)
register_jit_entry("admit_spec", _admit_spec)
register_jit_entry("write_slots", _write_slots)
register_jit_entry("decode_chunk", _decode_chunk)


class SlotEngine:
    """Continuous-batching generation engine with spec-prefix admission."""

    def __init__(self, params, cfg: ModelConfig, gen: GenerateConfig, *,
                 num_slots: int, prompt_width: int, spec_prefix: bool = False,
                 log_lenience: float = 0.0, chunk_steps: int = 8,
                 draft=None, mesh=None,
                 faults: Optional[FaultPlan] = None,
                 deadline_steps: Optional[int] = None,
                 max_queue: Optional[int] = None, overflow: str = "reject",
                 retry_backoff: Optional[BackoffConfig] = None,
                 tracer=None, ledger=None, obs_label: str = ""):
        assert M.supports_slot_serving(cfg), \
            "slot serving needs an attention-only trunk without modality " \
            "extras — use fixed-batch generate otherwise"
        self.params, self.cfg, self.gen = params, cfg, gen
        self.P = int(prompt_width)
        self.N = int(gen.max_new_tokens)
        self.spec_prefix = bool(spec_prefix)
        self.log_lenience = float(log_lenience)
        self.chunk_steps = max(1, int(chunk_steps))
        # §9 continuation draft engine: a DraftConfig switches _run_chunk
        # from `chunk_steps` single-token scans to one draft-verify block
        # per chunk, with per-slot n-gram sources / length controllers
        self.draft = draft if (draft is not None and draft.enabled) else None
        # One engine serves ONE data shard: its decode batch stays whole and
        # only the KV head axis (and the params the caller pre-sharded)
        # spread over the mesh's ``model`` axis.  Data parallelism lives one
        # level up — MeshSlotServer runs one engine per data-shard submesh
        # (DESIGN.md §8).
        self.mesh = mesh
        # context ends at write_base; decode token t lands at write_base + t
        # (vanilla: prefill layout [0, P); spec: compacted layout [0, P+N));
        # drafted engines add draft_k headroom for the block write (§9)
        self.write_base = self.P + (self.N if spec_prefix else 0)
        self.cache_len = self.write_base + self.N + \
            (self.draft.draft_k if self.draft else 0)

        B = int(num_slots)
        if self.draft:
            from repro.core.metrics import DraftStats
            from repro.drafting import DraftController, NGramDraftSource
            self._draft_source = NGramDraftSource(self.draft, B)
            self._draft_ctrl = DraftController(self.draft, B)
            self.draft_stats = DraftStats()
        self.caches = self._make_caches(B)
        if mesh is not None:
            from repro.distributed.mesh import shard_caches
            self.caches = shard_caches(cfg, self.caches, mesh, batch=False)
        self.scheduler = SlotScheduler(B, max_queue=max_queue,
                                       overflow=overflow)
        # §10 hardening state: engine-default deadline (a request's own
        # deadline_steps wins), the injected fault schedule, and the
        # pending targeted faults held until their request is in a slot
        self.deadline_steps = deadline_steps
        self.faults = faults
        self.fault_stats = FaultStats()
        # §12 backoff adoption: with a BackoffConfig, a reclaimed request
        # is NOT resubmitted immediately — it is held until the engine
        # step clock passes its exponential-backoff due step (base/factor
        # measured in engine steps), so repeated failures stop hammering
        # the same slot cycle.  None (the default) keeps the §10 behaviour
        # and existing kill-resume snapshots bit-identical.
        self.retry_backoff = retry_backoff
        self._retry_hold: List[Tuple[int, Request]] = []
        self.slot_age = np.zeros(B, np.int64)   # engine steps spent DECODING
        self._nan_due: set = set()              # request_ids awaiting nan
        self._stall_due: Dict[int, int] = {}    # request_id -> phantom steps
        self._draft_exc_due: set = set()        # request_ids awaiting exc
        self.cur_tok = np.zeros(B, np.int32)
        self.cur_lp = np.zeros(B, np.float32)
        self.done = np.ones(B, bool)
        self.count = np.zeros(B, np.int32)
        self.budget = np.zeros(B, np.int32)
        self.next_pos = np.zeros(B, np.int32)
        self.write_idx = np.full(B, self.write_base, np.int32)
        self.keys = np.zeros((B, 2), np.uint32)
        self._acc_tok: List[List[np.ndarray]] = [[] for _ in range(B)]
        self._acc_lp: List[List[np.ndarray]] = [[] for _ in range(B)]
        # §14: whether a slot's pending carry token is a free bonus sample
        # (previous drafted macro-step fully accepted).  Ledger bookkeeping
        # only — deliberately NOT in state_dict (the ledger isn't either)
        self._carry_bonus = np.zeros(B, bool)
        self._slot_n = np.zeros(B, np.int32)
        self._slot_draft_len = np.zeros(B, np.int32)
        self._slot_full_reuse = np.zeros(B, bool)
        self._slot_prefix_lp: List[Optional[np.ndarray]] = [None] * B
        self.responses: Dict[int, Response] = {}
        self.steps = 0                      # engine decode steps elapsed
        self.time_admit = 0.0
        self.time_slot_write = 0.0
        self.time_decode = 0.0
        # §11 observatory: the tracer draws request/engine lanes, the
        # engine-owned registry holds the latency histograms stats() can't
        # derive from counters (TTFT, queue wait, per-token decode time).
        # Both are inert by default — every recording call early-returns on
        # NULL_TRACER and histograms only fill where observe() runs, so the
        # clean path takes no extra clock reads or syncs (timestamps below
        # reuse the perf_counter values the time_* accounting already takes).
        self.tracer = tracer if tracer is not None else get_tracer()
        # §14 provenance ledger + decision log: host-side sinks, inert by
        # default (NULL_LEDGER / NULL_DECISION_LOG early-return everywhere),
        # and never consulted inside jit'd code — the zero-overhead contract
        # extends to byte-identical lowered HLO with or without them
        self.ledger = ledger if ledger is not None else get_ledger()
        self.decisions = get_decision_log()
        self.obs_label = str(obs_label)     # "shard<i>/" under a mesh server
        self._etrack = f"{self.obs_label}engine"
        self.metrics = MetricsRegistry()
        self._t0 = time.perf_counter()

    # ------------------------------------------------------------- frontend

    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def _abs(self, rel: float) -> float:
        """Engine-relative seconds → the tracer's perf_counter timeline."""
        return self._t0 + rel

    def submit(self, req: Request) -> None:
        assert len(req.prompt) <= self.P, (len(req.prompt), self.P)
        assert 0 <= req.max_new_tokens <= self.N, req.max_new_tokens
        shed = self.scheduler.submit(req, now=self._now())
        if shed is not None:
            # backpressure acted: the shed request resolves immediately with
            # an empty, explicitly-marked response (§10) — callers waiting
            # on it see a terminal state instead of a hang
            self.fault_stats.add(failed=1)
            self.responses[shed.request_id] = Response(
                request_id=shed.request_id, tokens=np.zeros(0, np.int32),
                logprobs=np.zeros(0, np.float32), length=0,
                finish_reason=FINISH_SHED, slot=-1, retries=shed.retries)

    def _release_retries(self) -> None:
        """Re-queue held backoff retries whose due step has passed (§12).
        Bypasses backpressure the same way an immediate resubmit does —
        a retry holds no NEW work."""
        if not self._retry_hold:
            return
        now = self._now()
        due = [r for d, r in self._retry_hold if d <= self.steps]
        self._retry_hold = [(d, r) for d, r in self._retry_hold
                            if d > self.steps]
        for req in due:
            self.scheduler.resubmit(req, now=now)

    def run(self, arrivals: Optional[Iterable[Tuple[int, Request]]] = None,
            max_chunks: Optional[int] = None) -> Dict[int, Response]:
        """Drive the loop until queue + slots drain (and arrivals exhaust).

        arrivals: optional (due_step, Request) stream sorted by due_step —
        requests arriving while the engine runs; the loop idles forward to
        the next due step when it would otherwise drain.
        """
        it = iter(arrivals) if arrivals is not None else None
        nxt = next(it, None) if it is not None else None
        chunks = 0
        while True:
            self._apply_faults()       # may raise EngineKilled (kind 'kill')
            self._release_retries()    # held backoff retries now due
            while nxt is not None and nxt[0] <= self.steps:
                self.submit(nxt[1])
                nxt = next(it, None)
            self._admit()
            if self.scheduler.idle:
                if self._retry_hold:   # backoff holds are pending work:
                    due = min(d for d, _ in self._retry_hold)
                    if nxt is not None:
                        due = min(due, int(nxt[0]))
                    self.steps = max(self.steps, due)      # idle fast-forward
                    continue
                if nxt is None:
                    break
                self.steps = max(self.steps, int(nxt[0]))  # idle fast-forward
                continue
            self._run_chunk()
            self._harvest()
            self._enforce_deadlines()
            chunks += 1
            if max_chunks is not None and chunks >= max_chunks:
                break
        return self.responses

    def metrics_registry(self) -> MetricsRegistry:
        """The engine's full telemetry as ONE typed registry (§11).

        Every scheduler lifecycle counter, §9 draft counter and §10 fault
        counter lands here with its merge semantics attached — counters
        sum, peak gauges max, ratios re-derive from summed parts — so
        ``MeshSlotServer`` gathers shards by a single type-driven
        ``MetricsRegistry.merge`` instead of a hand-listed key walk (the
        schema-drift fix: a new counter can no longer silently vanish from
        the gathered view).  ``stats()`` is just ``as_dict()`` of this.
        """
        from repro.core.metrics import DraftStats
        sch = self.scheduler
        reg = MetricsRegistry()
        # shape/config gauges (sum across shards where extensive)
        reg.set("num_slots", float(sch.num_slots), agg="sum")
        reg.set("num_shards", 1.0, agg="sum")
        reg.set("pending", float(len(sch.queue)), agg="sum")
        reg.set("max_queue", float(sch.max_queue or 0), agg="sum")
        reg.set("engine_steps", float(self.steps), agg="max")
        reg.set("wall_time", self._now(), agg="max")
        # scheduler lifecycle counters
        reg.inc("submitted", sch.submitted)
        reg.inc("admitted", sch.admitted)
        reg.inc("completed", sch.completed)
        reg.inc("busy_slot_steps", sch.busy_slot_steps)
        reg.inc("total_slot_steps", sch.total_slot_steps)
        reg.inc("queue_wait_total", sch.queue_wait_total)
        reg.inc("serve_time_total", sch.serve_time_total)
        reg.inc("timeouts", sch.timeouts)
        reg.inc("quarantined_requests", sch.quarantines)
        reg.inc("retried_requests", sch.retries)
        reg.inc("shed_requests", sch.sheds)
        reg.inc("rejected_requests", sch.rejected)
        reg.ratio("occupancy", "busy_slot_steps", "total_slot_steps")
        reg.ratio("mean_queue_wait", "queue_wait_total", "completed")
        reg.ratio("mean_serve_time", "serve_time_total", "completed")
        # engine throughput counters
        reg.inc("generated_tokens",
                sum(r.length for r in self.responses.values()))
        reg.inc("reused_tokens",
                sum(r.n_accepted for r in self.responses.values()))
        reg.inc("admit_time", self.time_admit)
        reg.inc("slot_write_time", self.time_slot_write)
        reg.inc("decode_time", self.time_decode)
        # §9 draft telemetry (zeros for undrafted engines, so the stats
        # schema is uniform across engine modes and mesh shards); the
        # legacy ratio names stay, re-derived from the summed counters
        ds = self.draft_stats if self.draft else DraftStats()
        reg.inc("draft_proposed", ds.proposed)
        reg.inc("draft_accepted", ds.accepted)
        reg.inc("decode_forwards", ds.forwards)
        reg.inc("decode_emitted", ds.emitted)
        reg.inc("draft_forwards", ds.draft_forwards)
        reg.ratio("accept_rate", "draft_accepted", "draft_proposed")
        reg.ratio("mean_draft_len", "draft_proposed", "draft_forwards")
        reg.ratio("tokens_per_forward", "decode_emitted", "decode_forwards")
        # §10 recovery telemetry under the uniform fault_ schema: the
        # engine-owned counters plus a mirror of the scheduler's lifecycle
        # counters, so one prefix carries the whole failure story
        fs = FaultStats(**{k: getattr(self.fault_stats, k)
                           for k in FaultStats.FIELDS})
        fs.timeouts = sch.timeouts
        fs.retries = sch.retries
        fs.sheds = sch.sheds
        fs.rejected = sch.rejected
        for k, v in fs.as_dict().items():
            reg.inc(k, v)
        # §14 sentinels: per-entry jit compile counts and backend memory
        # stats (the jit caches and device are process-global, so gauges
        # with agg="max" merge shard registries without double-counting;
        # memory gauges simply don't appear on backends that report None)
        record_compile_gauges(reg)
        record_device_memory(reg)
        # §14 provenance tallies — the ledger is process-global too
        if self.ledger.enabled:
            for cname, nv in self.ledger.counts_dict().items():
                reg.set(f"ledger.tokens_{cname}", float(nv), agg="max")
        # §11 latency histograms accumulated by the serving loop itself
        reg.merge(self.metrics)
        return reg

    def stats(self) -> Dict[str, float]:
        return self.metrics_registry().as_dict()

    # ------------------------------------------------------------ admission

    def _pad_group(self, rows: List[np.ndarray]) -> np.ndarray:
        """Stack + pad a group to num_slots rows by duplicating row 0."""
        B = self.scheduler.num_slots
        rows = rows + [rows[0]] * (B - len(rows))
        return np.stack(rows)

    # Layout hooks, overridden by PagedSlotEngine (DESIGN.md §13).  The
    # dense engine's behaviour is the identity on all four.

    def _make_caches(self, B: int):
        """Build the persistent decode caches (dense slabs by default)."""
        return M.init_cache(self.cfg, B, self.cache_len)

    def _admit_cfg(self) -> ModelConfig:
        """Config the admission jits build their throwaway caches with.
        The paged engine admits DENSELY (identical device programs to the
        dense engine) and re-pages at the slot write."""
        return self.cfg

    def _register_groups(self, group, out) -> None:
        """Post-admission hook: the paged engine registers each new GRPO
        group's prompt blocks + seed logits here for CoW sharing."""

    def _on_slot_freed(self, slot: int) -> None:
        """A request left ``slot`` (completed or reclaimed); the paged
        engine releases its block-table row here."""

    def _admit(self) -> None:
        while True:
            group = self.scheduler.reserve(self._now())
            if not group:
                return
            self._admit_group(group)

    def _prep_prompts(self, reqs: List[Request]):
        prom = np.zeros((len(reqs), self.P), np.int32)
        mask = np.zeros((len(reqs), self.P), bool)
        for j, r in enumerate(reqs):
            L = len(r.prompt)
            prom[j, self.P - L:] = np.asarray(r.prompt, np.int32)
            mask[j, self.P - L:] = True
        return prom, mask

    def _admit_group(self, group: List[Tuple[int, Request]]) -> None:
        t0 = time.perf_counter()
        B = self.scheduler.num_slots
        slots = [s for s, _ in group]
        reqs = [r for _, r in group]
        prom, mask = self._prep_prompts(reqs)
        prompts = self._pad_group(list(prom))
        masks = self._pad_group(list(mask))
        keys = self._pad_group([np.asarray(r.key, np.uint32) for r in reqs])

        dn = np.zeros((len(group),), np.int32)
        if self.spec_prefix:
            dt = np.zeros((len(group), self.N), np.int32)
            dl = np.zeros((len(group), self.N), np.float32)
            de = np.zeros((len(group),), bool)
            for j, r in enumerate(reqs):
                if r.has_draft:
                    L = min(len(r.draft_tokens), self.N)
                    dt[j, :L] = r.draft_tokens[:L]
                    dl[j, :L] = r.draft_logprobs[:L]
                    dn[j] = L
                    de[j] = r.draft_eos and L == len(r.draft_tokens)
            vkeys = self._pad_group(
                [np.asarray(r.verify_key, np.uint32) for r in reqs])
            out = _admit_spec(
                self.params, self._admit_cfg(), self.gen,
                jnp.asarray(prompts),
                jnp.asarray(masks), jnp.asarray(self._pad_group(list(dt))),
                jnp.asarray(self._pad_group(list(dl))),
                jnp.asarray(self._pad_group(list(dn))),
                jnp.asarray(self._pad_group(list(de))),
                jnp.asarray(vkeys), jnp.asarray(keys),
                self.log_lenience, mesh=self.mesh)
        else:
            out = _admit_vanilla(self.params, self._admit_cfg(), self.gen,
                                 jnp.asarray(prompts), jnp.asarray(masks),
                                 jnp.asarray(keys), mesh=self.mesh)
        jax.block_until_ready(out["tok0"])
        t1 = time.perf_counter()
        self.time_admit += t1 - t0

        slot_ids = np.array(slots + [slots[0]] * (B - len(slots)),
                            np.int32)
        self.caches = self._write_admitted(out["caches"], slot_ids)
        jax.block_until_ready(jax.tree.leaves(self.caches)[0])
        t2 = time.perf_counter()
        self.time_slot_write += t2 - t1

        # §11: admit/slot-write timings reuse t0/t1/t2 — the clock
        # reads the time_* accounting above already took
        self.metrics.observe("serve.admit_ms", (t1 - t0) * 1e3)
        self.metrics.observe("serve.slot_write_ms", (t2 - t1) * 1e3)
        tr = self.tracer
        if tr.enabled:
            tr.complete("admit", self._etrack, t0, t1, cat="admit",
                        rows=len(group))
            tr.complete("slot_write", self._etrack, t1, t2, cat="admit")

        self._register_groups(group, out)
        tok0 = np.asarray(out["tok0"])
        lp0 = np.asarray(out["lp0"])
        npos = np.asarray(out["next_pos"])
        nkeys = np.asarray(out["keys"])
        n = np.asarray(out["n"]) if self.spec_prefix else \
            np.zeros(B, np.int32)
        fr = np.asarray(out["full_reuse"]) if self.spec_prefix else \
            np.zeros(B, bool)
        lp_curr = np.asarray(out["lp_curr"]) if self.spec_prefix else None
        self._apply_admission(group, tok0, lp0, npos, nkeys, n, fr,
                              lp_curr, dn, t0, t1)
        # full-reuse / zero-budget admissions finish without decoding;
        # harvesting them here lets the loop keep back-filling
        self._harvest()

    def _write_admitted(self, src_caches, slot_ids: np.ndarray):
        """Scatter the admission caches into the persistent batch."""
        return _write_slots(self.cfg, self.caches, src_caches,
                            jnp.asarray(slot_ids),
                            pad_src=self.draft.draft_k if self.draft else 0,
                            mesh=self.mesh)

    def _apply_admission(self, group, tok0, lp0, npos, nkeys, n, fr,
                         lp_curr, dn, t0: float, t1: float) -> None:
        """Per-request host bookkeeping after an admission (any path):
        state vectors, telemetry, draft-source reset, activation.  Arrays
        are indexed by the request's position ``j`` in ``group``."""
        tr = self.tracer
        led = self.ledger
        for j, (slot, req) in enumerate(group):
            nj = int(n[j])
            budget = max(0, req.max_new_tokens - nj)
            if led.enabled:
                # §14: (re)build the provenance plane.  The accepted prefix
                # splits at the caller's draft boundary: up to base it is
                # SPEC-RL reuse; past it, the request's own re-verified
                # partial output from a previous occupancy (§10 retry)
                base = max(0, int(req.base_draft_len))
                led.begin_row(req.request_id, len(req.prompt),
                              prompt_cat=self._prompt_category(req))
                led.append(req.request_id, REUSED_PREFIX, min(nj, base))
                led.append(req.request_id,
                           led.retry_category(req.request_id),
                           nj - min(nj, base))
            # §11 per-request admission telemetry: queue wait, TTFT
            # (queued → seed token, which admission just produced) and
            # the SPEC-RL reuse length.  Span endpoints are the
            # engine-relative stamps the scheduler already recorded.
            self.metrics.observe("serve.queue_wait_ms",
                                 (req.admitted_at - req.queued_at) * 1e3)
            self.metrics.observe(
                "serve.ttft_ms",
                ((t1 - self._t0) - req.queued_at) * 1e3)
            if self.spec_prefix:
                self.metrics.observe("serve.reuse_len", nj)
            if tr.enabled and tr.sampled(req.request_id):
                lane = f"{self.obs_label}req/{req.request_id}"
                tr.complete("queued", lane, self._abs(req.queued_at),
                            self._abs(req.admitted_at), cat="queue",
                            retries=req.retries)
                tr.complete("admit", lane, t0, t1, cat="admit",
                            slot=slot, n_accepted=nj)
            self.cur_tok[slot] = tok0[j]
            self.cur_lp[slot] = lp0[j]
            self.count[slot] = 0
            self.budget[slot] = budget
            self.next_pos[slot] = npos[j]
            self.write_idx[slot] = self.write_base
            self.keys[slot] = nkeys[j]
            self.slot_age[slot] = 0     # deadline clock is per-occupancy
            self.done[slot] = bool(fr[j]) or budget <= 0
            self._acc_tok[slot] = []
            self._acc_lp[slot] = []
            self._carry_bonus[slot] = False   # seed sample is priced fresh
            self._slot_n[slot] = nj
            self._slot_draft_len[slot] = int(dn[j]) if self.spec_prefix \
                else 0
            self._slot_full_reuse[slot] = bool(fr[j])
            self._slot_prefix_lp[slot] = lp_curr[j] if lp_curr is not None \
                else None
            if self.draft:
                # n-gram index over prompt ⊕ accepted prefix, shadowing
                # the request's sibling corpus (DESIGN.md §9)
                ctx = list(np.asarray(req.prompt, np.int32))
                if self.spec_prefix and req.has_draft:
                    ctx.extend(np.asarray(req.draft_tokens[:nj],
                                          np.int32))
                self._draft_source.reset(slot, ctx, req.ngram_corpus)
                self._draft_ctrl.reset(slot)
            self.scheduler.activate(slot)

    def _prompt_category(self, req: Request) -> int:
        """Provenance of the prompt plane — the paged engine overrides this
        for CoW followers whose prompt blocks are mapped, not prefilled
        (§13 / §14)."""
        return PROMPT

    def _pool_pressure(self) -> float:
        """KV backing-store pressure in [0, 1] — 0 for dense slabs (they
        cannot run dry); the paged engine reports block-pool occupancy."""
        return 0.0

    # ---------------------------------------------------------- decode loop

    def _run_chunk(self, steps: Optional[int] = None) -> None:
        if self.draft:
            return self._run_draft_chunk()
        steps = steps or self.chunk_steps
        live = [s for s in self.scheduler.active if not self.done[s]]
        busy = len(live)
        # §10 fault hook: corrupt the logits of pending nan targets on the
        # first step of this chunk (−1 = never; the clean-path constant)
        inject = np.full(self.scheduler.num_slots, -1, np.int32)
        for slot, req in self.scheduler.active.items():
            if req.request_id in self._nan_due and not self.done[slot]:
                self._nan_due.discard(req.request_id)
                inject[slot] = 0
        t0 = time.perf_counter()
        out = _decode_chunk(
            self.params, self.cfg, self.gen, self.caches,
            jnp.asarray(self.cur_tok), jnp.asarray(self.cur_lp),
            jnp.asarray(self.done), jnp.asarray(self.count),
            jnp.asarray(self.budget), jnp.asarray(self.next_pos),
            jnp.asarray(self.write_idx), jnp.asarray(self.keys),
            jnp.asarray(inject), steps=steps, mesh=self.mesh)
        self.caches = out["caches"]
        toks = np.asarray(out["tokens"])            # (B, steps)
        lps = np.asarray(out["logprobs"])
        count0 = self.count
        t1 = time.perf_counter()
        self.time_decode += t1 - t0
        for name in ("cur_tok", "cur_lp", "done", "count", "next_pos",
                     "write_idx", "keys"):
            # np.array (not asarray): jax arrays view as read-only and the
            # admission path writes these in place
            setattr(self, name, np.array(out[name]))
        # §11 chunk telemetry: t0/t1 are the stamps time_decode already
        # takes; emitted counts come from the np state just harvested
        emitted = int((self.count[live] - count0[live]).sum()) if live else 0
        self.metrics.observe("serve.decode_chunk_ms", (t1 - t0) * 1e3)
        self.metrics.observe("serve.decode_step_ms", (t1 - t0) / steps * 1e3)
        if emitted > 0:
            self.metrics.observe("serve.token_ms", (t1 - t0) / emitted * 1e3)
        tr = self.tracer
        if tr.enabled:
            tr.complete("decode_chunk", self._etrack, t0, t1, cat="decode",
                        steps=steps, busy=busy, emitted=emitted)
            for slot in live:
                req = self.scheduler.active[slot]
                if tr.sampled(req.request_id):
                    tr.complete("decode_chunk",
                                f"{self.obs_label}req/{req.request_id}",
                                t0, t1, cat="decode", slot=slot)
        for slot in self.scheduler.active:
            self._acc_tok[slot].append(toks[slot])
            self._acc_lp[slot].append(lps[slot])
            self.slot_age[slot] += steps
        if self.ledger.enabled:
            # §14: the chunk's valid emission per slot is the count delta
            # (the accumulators above keep full chunk rows and trim at
            # harvest; the ledger must not)
            for slot, req in self.scheduler.active.items():
                self.ledger.append(req.request_id, FRESH,
                                   int(self.count[slot]) - int(count0[slot]))
        self.steps += steps
        self.scheduler.tick(busy, steps)
        # §10 quarantine: rows the in-chunk guard pulled out (their valid
        # prefix is in _acc; the corrupted sample was never stored) leave
        # the decode batch before harvest sees them as completions
        quar = np.asarray(out["quarantined"])
        for slot in [s for s in list(self.scheduler.active) if quar[s]]:
            self.fault_stats.add(nan_events=1)
            self._reclaim(slot, FINISH_QUARANTINE)

    def _run_draft_chunk(self) -> None:
        """One §9 draft-verify macro-step over all slots.

        The device program is the SAME jit'd ``drafting.step.draft_step``
        the fixed-batch drafted loops run — per-row write offsets / budgets
        / PRNG streams are the machinery this engine already carries, so a
        slot absorbs a variable-length accept exactly like a fixed-batch
        row (and greedy output stays token-identical, tested)."""
        from repro.drafting.step import block_width, draft_step
        K = self.draft.draft_k
        B = self.scheduler.num_slots
        busy = sum(1 for s in self.scheduler.active if not self.done[s])
        dt = np.zeros((B, K), np.int32)
        dl = np.zeros((B,), np.int32)
        dec = self.decisions
        feats: Dict[int, Dict[str, float]] = {}
        for slot in self.scheduler.active:
            if self.done[slot]:
                continue
            req = self.scheduler.active[slot]
            if req.draft_off:
                continue                # degraded row: plain (B, 2) decode
            try:
                # §10 fault hook: a targeted draft-source exception, then
                # the same guard any REAL proposal error falls into —
                # drafting dies for this row, the request decodes on
                if req.request_id in self._draft_exc_due:
                    self._draft_exc_due.discard(req.request_id)
                    raise RuntimeError("injected draft-source fault")
                k_s = self._draft_ctrl.draft_len(slot)
                d = self._draft_source.propose(slot, k_s,
                                               pending=int(self.cur_tok[slot]))
            except Exception:
                self.fault_stats.add(draft_errors=1, draft_disabled=1)
                req.draft_off = True
                continue
            dt[slot, :len(d)] = d
            dl[slot] = len(d)
            if dec.enabled:
                # §14 decision record, feature half: everything the length
                # controller could have looked at, captured pre-step from
                # host state the loop already holds.  surprisal is the
                # single-sample entropy estimate -logp of the pending token
                # (full logits never reach the host here, by design)
                feats[slot] = {
                    "surprisal": -float(self.cur_lp[slot]),
                    "position": float(self.next_pos[slot]),
                    "accept_ema": float(self._draft_ctrl.rate[slot]),
                    "draft_k": float(len(d)),
                    "draft_source": SOURCE_NGRAM,
                    "queue_depth": float(len(self.scheduler.queue)),
                    "slot_age": float(self.slot_age[slot]),
                    "pool_pressure": self._pool_pressure(),
                }
        # bucketed block width (drafting/step.py:block_width): the forward
        # narrows with the controller's draft lengths; u_width = draft_k
        # keeps per-request streams independent of co-batched buckets
        K_step = block_width(int(dl.max()), K)
        t0 = time.perf_counter()
        out = draft_step(
            self.params, self.cfg, self.gen, self.caches,
            jnp.asarray(self.cur_tok), jnp.asarray(self.cur_lp),
            jnp.asarray(self.done), jnp.asarray(self.count),
            jnp.asarray(self.budget), jnp.asarray(self.next_pos),
            jnp.asarray(self.write_idx), jnp.asarray(self.keys),
            jnp.asarray(dt[:, :K_step]), jnp.asarray(dl), K=K_step,
            u_width=K, mesh=self.mesh)
        self.caches = out["caches"]
        toks = np.asarray(out["tokens"])            # (B, K+1)
        lps = np.asarray(out["logprobs"])
        emitted = np.asarray(out["emitted"])
        t1 = time.perf_counter()
        self.time_decode += t1 - t0
        for name in ("cur_tok", "cur_lp", "done", "count", "next_pos",
                     "write_idx"):
            setattr(self, name, np.array(out[name]))
        self.keys = np.array(out["keys"])
        accepted = np.asarray(out["accepted"])
        proposed = np.asarray(out["proposed"])
        # §11 draft macro-step telemetry (t0/t1 = the time_decode stamps):
        # the acceptance time series lives in the span args
        n_em = int(emitted.sum())
        self.metrics.observe("serve.draft_chunk_ms", (t1 - t0) * 1e3)
        if n_em > 0:
            self.metrics.observe("serve.token_ms", (t1 - t0) / n_em * 1e3)
        tr = self.tracer
        if tr.enabled:
            tr.complete("draft_chunk", self._etrack, t0, t1, cat="draft",
                        busy=busy, proposed=int(proposed.sum()),
                        accepted=int(accepted.sum()), emitted=n_em)
            for slot in self.scheduler.active:
                if self.done[slot] and not emitted[slot]:
                    continue
                req = self.scheduler.active[slot]
                if tr.sampled(req.request_id):
                    tr.complete("draft_chunk",
                                f"{self.obs_label}req/{req.request_id}",
                                t0, t1, cat="draft", slot=slot,
                                proposed=int(proposed[slot]),
                                accepted=int(accepted[slot]),
                                emitted=int(emitted[slot]))
        quarantined: List[int] = []
        led = self.ledger
        for slot in self.scheduler.active:
            req = self.scheduler.active[slot]
            m = int(emitted[slot])
            # §10 non-finite guard, host-side for drafted chunks: scan the
            # block's logprobs; everything from the first bad index on is
            # poisoned and rolled back (injected nan poisons the block at 0)
            poison = m
            if req.request_id in self._nan_due and m > 0:
                self._nan_due.discard(req.request_id)
                poison = 0
            elif m > 0:
                bad = ~np.isfinite(lps[slot, :m])
                if bad.any():
                    poison = int(np.argmax(bad))
            if led.enabled and m:
                # §14: carry (fresh/bonus) + accepted-draft runs for this
                # block, clamped to the kept (un-poisoned) emission
                kept = min(poison, m)
                for cat, nrun in categorize_draft_block(
                        m, bool(self._carry_bonus[slot])):
                    if kept <= 0:
                        break
                    led.append(req.request_id, cat, min(nrun, kept))
                    kept -= nrun
            # a fully-accepted proposal makes the NEXT carry token a free
            # bonus sample (ledger bookkeeping only — never persisted,
            # like the ledger itself)
            self._carry_bonus[slot] = bool(
                proposed[slot] > 0 and accepted[slot] == proposed[slot])
            if poison < m:
                if poison:
                    self._acc_tok[slot].append(toks[slot, :poison])
                    self._acc_lp[slot].append(lps[slot, :poison])
                self.count[slot] -= m - poison      # drop the poisoned tail
                quarantined.append(slot)
                continue
            if m:
                self._acc_tok[slot].append(toks[slot, :m])
                self._acc_lp[slot].append(lps[slot, :m])
                self._draft_source.extend(slot, toks[slot, :m])
            self._draft_ctrl.update(slot, int(proposed[slot]),
                                    int(accepted[slot]))
        if dec.enabled and feats:
            # §14 decision record, outcome half: join the pre-step features
            # to what the verify actually returned (step_ms reuses the
            # t0/t1 stamps time_decode already took)
            step_ms = (t1 - t0) * 1e3
            for slot, f in feats.items():
                req = self.scheduler.active.get(slot)
                if req is None:
                    continue
                prop, acc = int(proposed[slot]), int(accepted[slot])
                m = int(emitted[slot])
                dec.record(req.request_id, self.steps, f, {
                    "proposed": prop, "accepted": acc,
                    "bonus": 1.0 if (prop > 0 and acc == prop and m > acc)
                    else 0.0,
                    "emitted": m, "step_ms": step_ms})
        for slot in self.scheduler.active:
            self.slot_age[slot] += 1
        self.draft_stats.add_step(forwards=busy,
                                  proposed=int(proposed.sum()),
                                  accepted=int(accepted.sum()),
                                  emitted=int(emitted.sum()),
                                  draft_forwards=int((dl > 0).sum()))
        self.steps += 1                     # one forward = one engine step
        self.scheduler.tick(busy, 1)
        for slot in quarantined:
            self.fault_stats.add(nan_events=1)
            self._reclaim(slot, FINISH_QUARANTINE)

    # ------------------------------------------------- §10 fault tolerance

    def _apply_faults(self) -> None:
        """Consume due FaultPlan events at a chunk boundary (the only points
        where host state is consistent).  Targeted events (nan / stall /
        draft_exc) are held pending until their request occupies a slot;
        bursts submit through the normal bounded-queue front door; a kill
        raises out of ``run`` — recovery is load_state_dict."""
        if self.faults is None:
            return
        step = self.steps
        for e in self.faults.due(step, "burst"):
            self.fault_stats.add(injected=1)
            for req in self.faults.next_burst_requests(e.count):
                self.submit(req)
        for e in self.faults.due(step, "nan"):
            self.fault_stats.add(injected=1)
            self._nan_due.add(e.request_id)
        for e in self.faults.due(step, "stall"):
            self.fault_stats.add(injected=1)
            self._stall_due[e.request_id] = e.count
        for e in self.faults.due(step, "draft_exc"):
            self.fault_stats.add(injected=1)
            self._draft_exc_due.add(e.request_id)
        if self.faults.due(step, "kill"):
            self.fault_stats.add(injected=1)
            raise EngineKilled(f"injected kill at engine step {step}")

    def _enforce_deadlines(self) -> None:
        """Reclaim slots whose request outstayed its decode-step deadline."""
        # pending stalls first: phantom aging lands the moment its target
        # is in a slot, deterministically tripping the deadline below
        for slot, req in self.scheduler.active.items():
            if req.request_id in self._stall_due and not self.done[slot]:
                self.slot_age[slot] += self._stall_due.pop(req.request_id)
        for slot in list(self.scheduler.active):
            req = self.scheduler.active[slot]
            if self.done[slot]:
                continue
            ddl = req.deadline_steps if req.deadline_steps is not None \
                else self.deadline_steps
            if ddl is not None and self.slot_age[slot] >= ddl:
                self._reclaim(slot, FINISH_TIMEOUT)

    def _reclaim(self, slot: int, reason: str) -> None:
        """Pull the request out of ``slot`` without finishing it (§10).

        Its valid partial output is preserved: a retry re-enters through
        the queue with that output grown onto its draft, so spec-prefix
        admission re-VERIFIES the tokens instead of regenerating them
        (one forward over [prompt | draft]).  Retries exhausted → a
        failure Response carrying the best-effort partial output.
        Quarantines also walk the degradation ladder: first strike turns
        the request's drafting off, a repeat steps the engine's decode
        impl down one rung.
        """
        req = self.scheduler.active[slot]
        cnt = max(0, int(self.count[slot]))
        toks = (np.concatenate(self._acc_tok[slot])[:cnt]
                if self._acc_tok[slot] else
                np.zeros(0, np.int32)).astype(np.int32)
        lps = (np.concatenate(self._acc_lp[slot])[:cnt]
               if self._acc_lp[slot] else
               np.zeros(0, np.float32)).astype(np.float32)
        n1 = int(self._slot_n[slot])
        plp = self._slot_prefix_lp[slot]
        if reason == FINISH_QUARANTINE:
            req.nan_strikes += 1
            self.fault_stats.add(quarantines=1)
            if not req.draft_off:
                req.draft_off = True        # ladder rung 1: stop speculating
                if self.draft:
                    self.fault_stats.add(draft_disabled=1)
            if req.nan_strikes >= 2:
                self._degrade_impl()        # rung 2: simpler decode kernel
        now = self._now()
        # §14: remember WHY the slot was lost — the partial output that
        # re-enters via spec-prefix verification on retry is attributed
        # RETRY_STITCHED (timeout/stall) or QUARANTINE_CLAMPED, not reuse
        self.ledger.note_retry(req.request_id, reason)
        self.scheduler.reclaim(slot, now=now, reason=reason)
        self._on_slot_freed(slot)
        tr = self.tracer
        _lane = f"{self.obs_label}req/{req.request_id}"
        if tr.enabled and tr.sampled(req.request_id):
            # fault instant on the request lane: quarantine / timeout / shed
            tr.event(reason, _lane, cat="fault", ts=self._abs(now),
                     slot=slot, retries=req.retries)
        if req.retries < req.max_retries:
            if self.spec_prefix:
                # accepted prefix ⊕ partial output becomes the retry draft;
                # lp_curr stands in for behaviour logprobs (both are this
                # policy's own logprobs, so re-verification accepts them)
                prev_t = (np.asarray(req.draft_tokens, np.int32)[:n1]
                          if req.draft_tokens is not None
                          else np.zeros(0, np.int32))
                prev_l = (np.asarray(plp, np.float32)[:n1]
                          if plp is not None else np.zeros(0, np.float32))
                req.draft_tokens = np.concatenate([prev_t,
                                                   toks]).astype(np.int32)
                req.draft_logprobs = np.concatenate(
                    [prev_l, lps]).astype(np.float32)
                req.draft_eos = False
            if self.retry_backoff is not None:
                # §12: hold the retry until its backoff due step — the
                # request re-enters the queue via _release_retries once
                # the engine clock catches up (delay grows per retry)
                delay = self.retry_backoff.delay(req.retries)
                self._retry_hold.append(
                    (self.steps + max(0, math.ceil(delay)), req))
            else:
                self.scheduler.resubmit(req, now=now)
            if tr.enabled and tr.sampled(req.request_id):
                tr.event("retry", _lane, cat="fault", ts=self._abs(now),
                         retry=req.retries)
        else:
            toks2, lps2, orig = self._stitch(req, n1, plp, toks, lps)
            self.fault_stats.add(failed=1)
            if self.ledger.enabled and self.ledger.has_row(req.request_id):
                # conservation holds for failure responses too: the plane
                # covers prompt + caller prefix + best-effort continuation
                self.ledger.finalize(req.request_id,
                                     len(req.prompt) + orig + len(toks2))
            self.responses[req.request_id] = Response(
                request_id=req.request_id, tokens=toks2, logprobs=lps2,
                length=len(toks2), finish_reason=reason, n_accepted=orig,
                prefix_logprobs=plp,
                draft_len=int(self._slot_draft_len[slot]), slot=slot,
                queue_time=req.admitted_at - req.queued_at,
                serve_time=now - req.admitted_at, retries=req.retries)
            self.metrics.observe("serve.serve_ms",
                                 (now - req.admitted_at) * 1e3)
            self.metrics.observe("serve.retries_per_request", req.retries)
            if tr.enabled and tr.sampled(req.request_id):
                # retroactive whole-lifecycle span: queued → failed
                tr.complete("request", _lane, self._abs(req.queued_at),
                            self._abs(now), cat="request", reason=reason,
                            tokens=len(toks2), retries=req.retries)
        self.done[slot] = True
        self._acc_tok[slot] = []
        self._acc_lp[slot] = []
        self._slot_prefix_lp[slot] = None

    def _stitch(self, req: Request, n1: int, plp, toks, lps):
        """Split a serving session's output at the CALLER's draft boundary.

        ``n1`` is the final admission's accepted-prefix length; past
        ``base_draft_len`` it covers the request's own re-verified partial
        output, which belongs in the *continuation* — the Response contract
        (caller-draft prefix vs everything generated here) is retry-blind.
        For never-retried requests n1 <= base and this is the identity.
        """
        base = max(0, int(req.base_draft_len))
        orig = min(n1, base)
        if n1 > orig:
            toks = np.concatenate([np.asarray(req.draft_tokens,
                                              np.int32)[orig:n1], toks])
            lps = np.concatenate([np.asarray(plp,
                                             np.float32)[orig:n1], lps])
        return toks.astype(np.int32), lps.astype(np.float32), orig

    def _degrade_impl(self) -> None:
        """Step the decode-attention impl down one ladder rung (§10).

        Engine-wide by necessity — the impl is a static jit field — so it
        only fires on a *second* quarantine of the same request, after
        per-row degradation (drafting off) was not enough.  Costs one
        recompile of each device program; the clean path never pays it.
        """
        nxt = _IMPL_LADDER.get(self.cfg.decode_impl)
        if nxt is None:
            return
        self.cfg = self.cfg.replace(decode_impl=nxt)
        self.fault_stats.add(impl_fallbacks=1)

    # -------------------------------------------------------------- harvest

    def _harvest(self) -> List[Response]:
        eos = self.gen.eos_id
        finished = []
        # a slot still PREFILLING belongs to a partially-admitted group (the
        # paged engine admits leaders before CoW followers) — its done flag
        # is stale state from the previous occupant, not a finished request
        for slot in [s for s in self.scheduler.active
                     if self.done[s]
                     and self.scheduler.active[s].state == DECODING]:
            req = self.scheduler.active[slot]
            cnt = int(self.count[slot])
            toks = (np.concatenate(self._acc_tok[slot])[:cnt]
                    if self._acc_tok[slot] else np.zeros(0, np.int32))
            lps = (np.concatenate(self._acc_lp[slot])[:cnt]
                   if self._acc_lp[slot] else np.zeros(0, np.float32))
            if self._slot_full_reuse[slot]:
                reason = FINISH_FULL_REUSE
            elif cnt > 0 and toks[-1] == eos:
                reason = FINISH_EOS
            else:
                reason = FINISH_BUDGET
            now = self._now()
            # retry-blind response split (§10): re-verified partial output
            # from earlier attempts moves from the accepted prefix back
            # into the continuation (identity for never-retried requests)
            toks, lps, orig = self._stitch(req, int(self._slot_n[slot]),
                                           self._slot_prefix_lp[slot],
                                           toks, lps)
            if self.ledger.enabled and self.ledger.has_row(req.request_id):
                # §14 conservation invariant: the provenance plane exactly
                # partitions prompt ⊕ caller prefix ⊕ continuation
                self.ledger.finalize(req.request_id,
                                     len(req.prompt) + orig + len(toks))
                self.ledger.clear_retry(req.request_id)
            resp = Response(
                request_id=req.request_id, tokens=toks, logprobs=lps,
                length=len(toks),
                finish_reason=reason, n_accepted=orig,
                prefix_logprobs=self._slot_prefix_lp[slot],
                draft_len=int(self._slot_draft_len[slot]), slot=slot,
                queue_time=req.admitted_at - req.queued_at,
                serve_time=now - req.admitted_at, retries=req.retries)
            self.responses[req.request_id] = resp
            self.scheduler.complete(slot, now=now)
            self._on_slot_freed(slot)
            self.metrics.observe("serve.serve_ms", resp.serve_time * 1e3)
            self.metrics.observe("serve.retries_per_request", req.retries)
            tr = self.tracer
            if tr.enabled and tr.sampled(req.request_id):
                # retroactive whole-lifecycle span: queued → finished
                tr.complete("request",
                            f"{self.obs_label}req/{req.request_id}",
                            self._abs(req.queued_at), self._abs(now),
                            cat="request", reason=reason, tokens=len(toks),
                            n_accepted=orig, slot=slot, retries=req.retries)
            self._acc_tok[slot] = []
            self._acc_lp[slot] = []
            self._slot_prefix_lp[slot] = None
            finished.append(resp)
        return finished

    # ----------------------------------------------- exact kill-and-resume

    _VEC_FIELDS = ("cur_tok", "cur_lp", "done", "count", "budget",
                   "next_pos", "write_idx", "keys", "slot_age", "_slot_n",
                   "_slot_draft_len", "_slot_full_reuse")

    def state_dict(self) -> Dict:
        """Everything the decode loop's future depends on, as an all-array
        pytree (checkpoint/io.save_pytree-compatible).

        Covers the cache slabs, every per-slot state vector, the partial
        token accumulators, the scheduler (queued + in-flight requests,
        bit-exact), finished responses, the §9 draft state (controller
        EMAs, n-gram streams/corpora — the index is rebuilt on load, which
        is order-equivalent to the incremental indexing that built it) and
        all counters.  NOT covered, by design: params/config (the caller
        reconstructs the engine the same way it built it — asserted via
        meta) and the FaultPlan (a restored engine resumes clean).
        ``load_state_dict(state_dict())`` resumes token-identically
        (tests/serving/test_kill_resume.py).
        """
        st: Dict = {
            "meta": {
                "num_slots": np.int64(self.scheduler.num_slots),
                "prompt_width": np.int64(self.P),
                "max_new_tokens": np.int64(self.N),
                "spec_prefix": np.bool_(self.spec_prefix),
                "decode_impl": np.int64(
                    _IMPL_NAMES.index(self.cfg.decode_impl)),
                "steps": np.int64(self.steps),
                "elapsed": np.float64(self._now()),
                "time_admit": np.float64(self.time_admit),
                "time_slot_write": np.float64(self.time_slot_write),
                "time_decode": np.float64(self.time_decode),
            },
            "caches": jax.tree.map(np.asarray, self.caches),
            "vec": {k: np.asarray(getattr(self, k))
                    for k in self._VEC_FIELDS},
            "acc_tok": {str(s): np.concatenate(a).astype(np.int32)
                        for s, a in enumerate(self._acc_tok) if a},
            "acc_lp": {str(s): np.concatenate(a).astype(np.float32)
                       for s, a in enumerate(self._acc_lp) if a},
            "prefix_lp": {str(s): np.asarray(p, np.float32)
                          for s, p in enumerate(self._slot_prefix_lp)
                          if p is not None},
            "scheduler": self.scheduler.state_dict(),
            "responses": {str(rid): r.to_state()
                          for rid, r in self.responses.items()},
            "fault_stats": {k: np.int64(getattr(self.fault_stats, k))
                            for k in FaultStats.FIELDS},
            # §11: the latency histograms resume with the engine, so a
            # kill-and-resume run keeps monotonic counters and percentiles
            "obs": self.metrics.state_dict(),
        }
        if self._retry_hold:
            # §12 backoff holds: requests waiting out their retry delay are
            # in-flight state too — dropping them on resume would lose work.
            # Written only when non-empty, so default-config snapshots stay
            # bit-identical to their pre-backoff layout.
            st["retry_hold"] = {
                str(i): {"due": np.int64(d), "req": r.to_state()}
                for i, (d, r) in enumerate(self._retry_hold)}
        if self.draft:
            st["draft"] = {
                "rate": np.asarray(self._draft_ctrl.rate, np.float64),
                "stream": {str(s): np.asarray(v, np.int64)
                           for s, v in enumerate(self._draft_source._stream)},
                "corpus": {str(s): {str(j): np.asarray(seq, np.int32)
                                    for j, seq in enumerate(v)}
                           for s, v in
                           enumerate(self._draft_source._corpus)},
                "stats": {k: np.int64(getattr(self.draft_stats, k))
                          for k in ("forwards", "draft_forwards", "proposed",
                                    "accepted", "emitted")},
            }
        return st

    def load_state_dict(self, state: Dict) -> None:
        meta = state["meta"]
        assert int(meta["num_slots"]) == self.scheduler.num_slots and \
            int(meta["prompt_width"]) == self.P and \
            int(meta["max_new_tokens"]) == self.N and \
            bool(meta["spec_prefix"]) == self.spec_prefix, \
            "engine was constructed with a different shape than the snapshot"
        impl = _IMPL_NAMES[int(meta["decode_impl"])]
        if impl != self.cfg.decode_impl:   # resume mid-degradation-ladder
            self.cfg = self.cfg.replace(decode_impl=impl)
        caches = jax.tree.map(jnp.asarray, state["caches"])
        if self.mesh is not None:
            from repro.distributed.mesh import shard_caches
            caches = shard_caches(self.cfg, caches, self.mesh, batch=False)
        self.caches = caches
        for k in self._VEC_FIELDS:
            setattr(self, k, np.array(state["vec"][k]))
        self._slot_full_reuse = self._slot_full_reuse.astype(bool)
        self.done = self.done.astype(bool)
        B = self.scheduler.num_slots
        self._acc_tok = [[np.asarray(state["acc_tok"][str(s)], np.int32)]
                         if str(s) in state["acc_tok"] else []
                         for s in range(B)]
        self._acc_lp = [[np.asarray(state["acc_lp"][str(s)], np.float32)]
                        if str(s) in state["acc_lp"] else []
                        for s in range(B)]
        self._slot_prefix_lp = [
            np.asarray(state["prefix_lp"][str(s)], np.float32)
            if str(s) in state["prefix_lp"] else None for s in range(B)]
        self.scheduler.load_state_dict(state["scheduler"])
        self.responses = {int(rid): Response.from_state(rs)
                          for rid, rs in state["responses"].items()}
        for k in FaultStats.FIELDS:
            setattr(self.fault_stats, k, int(state["fault_stats"][k]))
        if "obs" in state:          # absent in pre-§11 snapshots
            self.metrics.load_state_dict(state["obs"])
        hold = state.get("retry_hold", {})   # absent in pre-§12 snapshots
        self._retry_hold = [
            (int(hold[str(i)]["due"]), Request.from_state(hold[str(i)]["req"]))
            for i in range(len(hold))]
        if self.draft and "draft" in state:
            d = state["draft"]
            self._draft_ctrl.rate = np.array(d["rate"], np.float64)
            for s in range(B):
                stream = [int(t) for t in np.asarray(d["stream"][str(s)])]
                corp = d["corpus"].get(str(s), {})
                corpus = [np.asarray(corp[str(j)], np.int32)
                          for j in range(len(corp))]
                # reset() re-registers corpus-then-stream in the same order
                # incremental indexing did, so the rebuilt suffix map is
                # identical and proposals resume bit-exactly
                self._draft_source.reset(s, stream, corpus)
            for k in ("forwards", "draft_forwards", "proposed", "accepted",
                      "emitted"):
                setattr(self.draft_stats, k, int(d["stats"][k]))
        self.steps = int(meta["steps"])
        self.time_admit = float(meta["time_admit"])
        self.time_slot_write = float(meta["time_slot_write"])
        self.time_decode = float(meta["time_decode"])
        self._t0 = time.perf_counter() - float(meta["elapsed"])
