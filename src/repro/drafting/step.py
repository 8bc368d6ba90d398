"""The jit'd draft-verify decode step shared by every drafted decode loop.

One macro-step replaces up to ``K + 1`` single-token decode steps with ONE
forward of a (K+1)-token block — [current token | K drafted tokens] — and
turns the drafts into kept output via rejection sampling:

1. **forward**: the block is written into the per-row cache slots
   [write_idx, write_idx + K] and attends through the short-multi-token
   flash-decode path (models/attention._decode_shaped) over each row's live
   bounds.  Draft padding and done rows carry position -1 (masked).
2. **verify**: draft token i is scored by the logits at block column i;
   acceptance reuses the ``kernels/spec_verify`` accept/first-reject
   reduction with ``lp_prev = 0`` (the n-gram proposal is a point mass) and
   zero lenience — accept g_i iff u_i <= p(g_i).  Under greedy
   (temperature <= 0) the log-ratio is built from the argmax directly
   (0 on match, -inf otherwise) with a constant u, so acceptance is exactly
   "draft == argmax" — bit-exact vanilla greedy, no float thresholds.
3. **accept / truncate**: vanilla ``_decode_loop`` done-semantics are
   replayed over the stored candidates [cur_tok | accepted drafts]: stop at
   the first eos or budget exhaustion.  Cache slots written beyond the kept
   tokens are invalidated (pos = -1); the next block overwrites them, so
   the cache is byte-equivalent (live region) to single-token decoding.
4. **correct**: the next carry token is sampled at block column n — from
   the residual distribution (draft masked out) on rejection, from the
   plain distribution on full acceptance (the "bonus" token) — via
   ``sampling.residual_sample``, whose emitted marginal is exactly p.

Per-row accepts advance per-row write offsets unevenly — the same
(write_idx, budget, count) machinery the serving slot engine already
carries, which is why this one device program serves ``drafted_generate``,
``drafted_resume`` AND the slot engine's draft chunks (DESIGN.md §9).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.engine.generate import GenerateConfig
from repro.engine.sampling import logprobs_of, residual_sample, split_key
from repro.models import model as M
from repro.models.config import ModelConfig

NEG_INF = -1e30


def _uniforms(key, B: int, K: int):
    """(B, K) acceptance uniforms from a (2,) or (B, 2) key."""
    if jnp.ndim(key) == 2:
        return jax.vmap(lambda k: jax.random.uniform(k, (K,)))(key)
    return jax.random.uniform(key, (B, K))


def _spec_verify(mesh, lp_curr, lp_prev, u, valid_len):
    if mesh is not None:
        from repro.distributed.shard_wrap import sharded_spec_verify
        return sharded_spec_verify(mesh, lp_curr, lp_prev, u, valid_len,
                                   0.0)
    from repro.kernels.spec_verify.ops import spec_verify
    return spec_verify(lp_curr, lp_prev, u, valid_len, 0.0)


def _invalidate_slots(caches, lo, hi):
    """pos = -1 on cache slots j with lo[b] <= j < hi[b] (rejected drafts)."""
    out = []
    for run in caches:
        sc = dict(run["self"])
        pos = sc["pos"]                               # (run, B, S)
        S = pos.shape[-1]
        j = jnp.arange(S, dtype=jnp.int32)[None, :]
        kill = (j >= lo[:, None]) & (j < hi[:, None])  # (B, S)
        sc["pos"] = jnp.where(kill[None], -1, pos)
        out.append({"self": sc})
    return out


def block_width(max_proposed: int, k_max: int) -> int:
    """The static draft width to compile this macro-step at: the power-of-
    two cover of the widest live proposal, capped at the engine's draft_k.

    The block forward is statically (K + 1) tokens wide whatever gets
    accepted, so proposing less only pays off if the compiled width
    shrinks with it — bucketing to powers of two keeps the number of jit
    variants at log2(draft_k) while letting the DraftController's
    adaptive lengths genuinely narrow the forward."""
    w = 1 << max(0, int(max_proposed) - 1).bit_length()
    return max(1, min(w, k_max))


@functools.partial(jax.jit, static_argnames=("cfg", "gen", "K", "u_width",
                                             "mesh"))
def draft_step(params, cfg: ModelConfig, gen: GenerateConfig, caches,
               cur_tok, cur_lp, done, count, budget, next_pos, write_idx,
               keys, draft_tokens, draft_len, *, K: int, u_width: int = 0,
               mesh=None):
    """One draft-verify macro-step for all B rows.

    cur_tok/cur_lp: (B,) carry token (sampled, not yet stored) and its
    behaviour log-prob; done/count/budget/next_pos: (B,) vanilla decode
    state (count counts STORED tokens, budget caps them); write_idx: (B,)
    per-row first free cache slot; keys: (2,) or (B, 2) PRNG;
    draft_tokens: (B, K) right-padded proposals; draft_len: (B,) int32.

    The caller must have allocated enough spare cache slots past the last
    token it will keep (model.pad_cache with the engine's draft_k >= K) —
    the block write is statically K + 1 wide whatever gets accepted.

    ``u_width`` (0 = K) fixes the width of the acceptance-uniform draw
    independently of the compiled block width: engines that bucket K per
    macro-step (``block_width``) pass their full draft_k here, so a row's
    acceptance draws — and therefore its sampled stream — do not depend on
    how wide its co-batched rows made the bucket (the same grouping-
    invariance contract the §6 slot engine rides).

    Returns dict with the advanced state plus:
      tokens/logprobs (B, K+1)  kept tokens this step, left-packed, padded
      emitted          (B,)     how many of those columns are real
      accepted         (B,)     raw rejection-sampling accepts (telemetry /
                                the DraftController signal)
      proposed         (B,)     drafts actually verified (0 for done rows)
    """
    assert K >= 1, K
    B = cur_tok.shape[0]
    bidx = jnp.arange(K + 1, dtype=jnp.int32)[None, :]
    eff_len = jnp.where(done, 0, draft_len.astype(jnp.int32))

    # ---- block forward: [cur_tok | drafts], one write + one attention ----
    tok_store = jnp.where(done, gen.pad_id, cur_tok)
    block = jnp.concatenate(
        [tok_store[:, None],
         jnp.where(jnp.arange(K, dtype=jnp.int32)[None, :] < eff_len[:, None],
                   draft_tokens, gen.pad_id)], axis=1)          # (B, K+1)
    valid = (~done[:, None]) & (bidx <= eff_len[:, None])
    pos_block = jnp.where(valid, next_pos[:, None] + bidx, -1)
    logits, caches = M.decode_step(
        params, cfg, block, pos_block, caches, write_idx,
        kv_length=write_idx + 1 + K, kv_start=write_idx - next_pos,
        mesh=mesh)                                              # (B, K+1, V)

    # ---- verify: block column i scores draft i -------------------------
    lp_draft = logprobs_of(logits[:, :K], draft_tokens,
                           gen.temperature, gen.top_p)          # (B, K)
    if gen.temperature <= 0.0:
        # greedy: accept iff draft == argmax, expressed as an exact log-
        # ratio (0 / -inf) against a constant uniform — keys stay unused,
        # mirroring sample()'s greedy branch
        am = jnp.argmax(logits[:, :K], axis=-1).astype(jnp.int32)
        lp_acc = jnp.where(am == draft_tokens, 0.0, NEG_INF)
        u = jnp.full((B, K), 0.5, jnp.float32)
    else:
        lp_acc = lp_draft
        keys, sub = split_key(keys)
        u = _uniforms(sub, B, max(u_width, K))[:, :K]
    n = _spec_verify(mesh, lp_acc, jnp.zeros_like(lp_acc), u,
                     eff_len)                                   # (B,)

    # ---- accept/truncate: replay vanilla done-semantics over the kept
    # candidates [cur_tok | draft[:n]] ----------------------------------
    avail = jnp.where(done, 0, 1 + n)
    is_stop = (block == gen.eos_id) | \
        ((count[:, None] + bidx + 1) >= budget[:, None])
    stop_in = is_stop & (bidx < avail[:, None])
    any_stop = stop_in.any(axis=1)
    first_stop = jnp.argmax(stop_in, axis=1).astype(jnp.int32)
    m = jnp.where(done, 0, jnp.where(any_stop, first_stop + 1, avail))
    done_next = done | any_stop

    lp_block = jnp.concatenate([cur_lp[:, None], lp_draft], axis=1)
    emit = bidx < m[:, None]
    toks_out = jnp.where(emit, block, gen.pad_id)
    lps_out = jnp.where(emit, lp_block, 0.0)

    # invalidate written-but-rejected slots; next block overwrites them
    caches = _invalidate_slots(caches, write_idx + m, write_idx + K + 1)

    # ---- correction / bonus sample at block column n -------------------
    nxt_logits = jnp.take_along_axis(
        logits, n[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    rejected = n < eff_len
    rej_tok = jnp.take_along_axis(
        draft_tokens, jnp.clip(n, 0, K - 1)[:, None], axis=1)[:, 0]
    keys, sub = split_key(keys)
    nxt, nlp = residual_sample(sub, nxt_logits, rej_tok, rejected,
                               gen.temperature, gen.top_p)

    return {
        "caches": caches,
        "cur_tok": jnp.where(done_next, cur_tok, nxt),
        "cur_lp": jnp.where(done_next, cur_lp, nlp),
        "done": done_next,
        "count": count + m,
        "next_pos": next_pos + m,
        "write_idx": write_idx + m,
        "keys": keys,
        "tokens": toks_out,
        "logprobs": lps_out,
        "emitted": m,
        "accepted": jnp.minimum(n, eff_len),
        "proposed": eff_len,
    }


# §14 recompile sentinel enrollment (obs/alerts.py): draft_step is shared
# by every drafted loop, so its cache size counts compiles for all of them
from repro.obs.alerts import register_jit_entry  # noqa: E402

register_jit_entry("draft_step", draft_step)
