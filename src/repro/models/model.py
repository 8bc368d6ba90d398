"""Top-level language model: embeddings, trunk, head, optional encoder
(whisper), optional MTP head (deepseek-v3), modality-frontend hooks.

Three entry points (all pure functions over a params pytree):

``forward``      training / scoring: full-sequence logits, no cache.
``prefill``      builds decode caches from a (left-padded) prompt.
``decode_step``  one token against the caches.

Frontends (audio frames / vision patches) are STUBS per the assignment: the
engine supplies precomputed embeddings of shape (B, P, d_model); here they are
simply placed in front of the token embeddings (vision) or consumed by the
encoder (audio).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .blocks import (apply_trunk, init_trunk_cache, make_trunk,
                     signature_runs)
from .config import ModelConfig
from .layers import (apply_dense, apply_rmsnorm, embed_init, make_dense,
                     make_rmsnorm, softcap, split_keys)
from .moe import apply_ffn


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# Forward-pass op counters (host-side).  Incremented at python level, so under
# jit they count *traces*; wrap a region in ``jax.disable_jit()`` to count the
# actual forwards executed — that is how the one-pass SPEC-RL benchmark/tests
# assert "prompt ⊕ accepted prefix is forwarded exactly once per step".
# ``decode_attn_inplace`` / ``decode_attn_sliced`` count decode-attention
# calls that read the stacked K/V (or MLA latent) cache in place / read one
# layer's view of it (models/attention._decode_attention, _mla_decode):
# which path a config takes.  ``decode_attn_tile_<width>`` counts the dense
# GQA kernel's calls by the slot tile it takes from the shapes.
OP_COUNTS = {"forward": 0, "prefill": 0, "decode_step": 0,
             "decode_attn_inplace": 0, "decode_attn_sliced": 0,
             "decode_attn_tile_128": 0, "decode_attn_tile_256": 0,
             "decode_attn_tile_512": 0}


def reset_op_counts() -> None:
    for k in OP_COUNTS:
        OP_COUNTS[k] = 0


def init_lm(key, cfg: ModelConfig) -> Dict[str, Any]:
    cfg.validate()
    dtype = _dt(cfg)
    ks = split_keys(key, 8)
    params: Dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "trunk": make_trunk(ks[1], cfg, dtype),
        "final_norm": make_rmsnorm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = make_dense(ks[2], cfg.d_model, cfg.vocab_size,
                                       False, dtype)
    if cfg.pos_embed == "learned":
        params["pos_table"] = embed_init(ks[3], cfg.max_seq_len, cfg.d_model, dtype)
    if cfg.encoder_layers:
        enc_cfg = cfg.replace(num_layers=cfg.encoder_layers, cross_attention=False,
                              num_experts=0, block_kind="attn", attn_period=0)
        params["encoder"] = {
            "trunk": make_trunk(ks[4], enc_cfg, dtype),
            "final_norm": make_rmsnorm(cfg.d_model, dtype),
        }
    if cfg.mtp:
        from .blocks import make_block
        params["mtp"] = {
            "proj": make_dense(ks[5], 2 * cfg.d_model, cfg.d_model, False, dtype),
            "block": make_block(ks[6], cfg, ("attn", False, False), dtype),
            "norm": make_rmsnorm(cfg.d_model, dtype),
        }
    return params


def count_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def _embed(params, cfg: ModelConfig, tokens, positions):
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    if cfg.pos_embed == "learned":
        pos = jnp.clip(positions, 0, cfg.max_seq_len - 1)
        x = x + params["pos_table"][pos].astype(x.dtype)
    valid = (positions >= 0)[..., None]
    return jnp.where(valid, x, 0.0)


def _logits(params, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        logits = x @ params["embed"].astype(x.dtype).T
    else:
        logits = apply_dense(params["lm_head"], x)
    return softcap(logits.astype(jnp.float32), cfg.logit_softcap)


def encode(params, cfg: ModelConfig, frames) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Whisper-style encoder over stub frame embeddings (B, F, d_model).

    Returns (encoder_out, encoder_positions)."""
    enc_cfg = cfg.replace(num_layers=cfg.encoder_layers, cross_attention=False,
                          num_experts=0, block_kind="attn", attn_period=0)
    B, F, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32), (B, F))
    x, _, _ = apply_trunk(params["encoder"]["trunk"], enc_cfg,
                          frames.astype(jnp.dtype(cfg.dtype)), pos, causal=False)
    x = apply_rmsnorm(params["encoder"]["final_norm"], x, cfg.norm_eps)
    return x, pos


def forward(params, cfg: ModelConfig, tokens, positions, *,
            encoder_out=None, encoder_positions=None, prefix_embeds=None,
            use_pallas: bool = False, return_hidden: bool = False,
            return_mtp: bool = False, compute_logits: bool = True):
    """Full-sequence teacher-forced forward.

    tokens: (B, T) int32; positions: (B, T) with -1 on padding.
    prefix_embeds: optional (B, P, d_model) — vision patches; caller's
    positions must already cover P + T (pass positions for the FULL sequence).
    Returns (logits over token slots only, aux dict).
    """
    OP_COUNTS["forward"] += 1
    x = _embed(params, cfg, tokens, positions if prefix_embeds is None
               else positions[:, prefix_embeds.shape[1]:])
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    x, _, aux = apply_trunk(params["trunk"], cfg, x, positions,
                            encoder_out=encoder_out,
                            encoder_positions=encoder_positions,
                            use_pallas=use_pallas)
    x = apply_rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if prefix_embeds is not None:
        x = x[:, prefix_embeds.shape[1]:]
    logits = _logits(params, cfg, x) if compute_logits else None
    if return_hidden:
        aux["hidden"] = x
    if cfg.mtp and return_mtp:
        aux["mtp_logits"] = _mtp_logits(params, cfg, x, tokens, positions if
                                        prefix_embeds is None else
                                        positions[:, prefix_embeds.shape[1]:])
    return logits, aux


def _mtp_logits(params, cfg: ModelConfig, hidden, tokens, positions):
    """DeepSeek-V3 multi-token prediction: predict t+2 from (h_t, emb_{t+1})."""
    from .blocks import apply_block
    emb_next = jnp.concatenate(
        [params["embed"][tokens[:, 1:]],
         jnp.zeros_like(params["embed"][tokens[:, :1]])], axis=1).astype(hidden.dtype)
    h = apply_dense(params["mtp"]["proj"],
                    jnp.concatenate([apply_rmsnorm(params["mtp"]["norm"], hidden,
                                                   cfg.norm_eps), emb_next], axis=-1))
    h, _, _ = apply_block(params["mtp"]["block"], cfg, ("attn", False, False),
                          h, positions)
    return _logits(params, cfg, h)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    return init_trunk_cache(cfg, batch, max_len, jnp.dtype(cfg.dtype))


def _paged_run_gather(sc, impl: str = "auto"):
    """Dense logical K/V view of one paged cache run.

    sc: run dict with pools (run, NB, Hkv, bs, D) / (run, NB, bs, r) and
    ``table`` (run, B, nb).  Returns {name: (run, B[, H], S, D)} for the
    K/V leaves with S = the logical (``pos``) width — exactly the buffers a
    dense cache would hold, which every dense cache op expects.  Routed
    through the ``paged_gather`` kernel op with heads folded into the block
    rows (the same flattening cache_roll uses)."""
    from repro.kernels.cache_gather.ops import paged_gather
    table = sc["table"]
    run_len, B, nb = table.shape
    S_log = sc["pos"].shape[-1]
    out = {}
    for name in ("k", "v", "ckv", "krope"):
        if name not in sc:
            continue
        pool = sc[name]
        NB = pool.shape[1]
        bs, D = pool.shape[-2], pool.shape[-1]
        r0 = jnp.arange(run_len, dtype=jnp.int32)[:, None, None]
        tab = (r0 * NB + table.astype(jnp.int32)).reshape(run_len * B, nb)
        if pool.ndim == 5:                       # GQA: (run, NB, Hkv, bs, D)
            Hkv = pool.shape[2]
            g = paged_gather(pool.reshape(run_len * NB, Hkv * bs, D), tab,
                             impl=impl)
            g = (g.reshape(run_len, B, nb, Hkv, bs, D)
                 .transpose(0, 1, 3, 2, 4, 5)
                 .reshape(run_len, B, Hkv, nb * bs, D)[..., :S_log, :])
        else:                                    # MLA: (run, NB, bs, r)
            g = paged_gather(pool.reshape(run_len * NB, bs, D), tab,
                             impl=impl)
            g = g.reshape(run_len, B, nb * bs, D)[..., :S_log, :]
        out[name] = g
    return out


def _pad_to_blocks(buf, nb: int, bs: int):
    """Zero-pad a dense logical buffer (..., S, D) to the block-rounded
    width nb*bs so it cuts into whole blocks for re-paging."""
    S = buf.shape[-2]
    if S == nb * bs:
        return buf
    pad = [(0, 0)] * buf.ndim
    pad[-2] = (0, nb * bs - S)
    return jnp.pad(buf, pad)


def supports_cache_realign(cfg: ModelConfig) -> bool:
    """Cache compaction needs every trunk layer to hold per-slot KV state.

    Recurrent blocks (mamba / rwkv) carry a single running state that cannot
    be rewound past rejected draft tokens, so they take the two-pass path."""
    from .config import ATTN
    return all(kind == ATTN for kind, _ in cfg.layer_plan())


def _roll_rows(buf, shift, impl):
    """Right-rotate ``buf`` (..., S, D) along axis -2, per-batch shift.

    buf: (run, B, S, D) or (run, B, H, S, D); shift: (B,) int32."""
    from repro.kernels.cache_gather.ops import cache_roll
    lead = buf.shape[:-2]                        # (run, B[, H])
    reps = 1
    for d in lead:
        reps *= d
    per_b = reps // (lead[0] * lead[1])          # heads folded after batch
    shift_r = jnp.tile(jnp.repeat(shift.astype(jnp.int32), per_b), lead[0])
    flat = buf.reshape((reps,) + buf.shape[-2:])
    return cache_roll(flat, shift_r, impl=impl).reshape(buf.shape)


def realign_decode_cache(cfg: ModelConfig, caches, shift, valid_len,
                         width: int, *, impl: str = "auto", mesh=None):
    """Compact verify-prefill caches to the left-aligned decode layout.

    After ``prefill`` over [left-padded prompt | right-padded draft] of width
    ``width``, row b's accepted context (p_len + n = ``valid_len[b]`` tokens)
    occupies the contiguous slot range [P - p_len, P + n).  Rotating the
    sequence axis right by ``shift[b] = width - (P + n[b])`` lands it at
    [width - valid_len, width) — exactly the layout ``prefill`` over the
    left-aligned tokens would have produced — and slot positions are
    rewritten in closed form (slots outside the valid range become -1, so
    position-masked attention ignores whatever K/V the rotation wrapped in).

    caches: trunk cache list (attention-only, see supports_cache_realign);
    shift / valid_len: (B,) int32; width: python int (the prefilled width).
    Returns the realigned cache pytree, ready for ``resume_from_cache`` with
    write_offset = width.

    Under a ``mesh`` the per-buffer roll runs inside a shard_map boundary
    over the batch (data) axis — each device rolls its local cache rows with
    a static per-shard shape — and the output is constrained back to the
    decode-cache layout (DESIGN.md §8).
    """
    assert supports_cache_realign(cfg), "realign needs attention-only trunks"
    roll = _roll_rows
    if mesh is not None:
        from repro.distributed.shard_wrap import (batch_axis_name,
                                                  batch_shardable,
                                                  shard_map_call)
        from jax.sharding import PartitionSpec as P

        def roll(buf, shift_, impl_):
            if not batch_shardable(mesh, buf.shape[1]):
                return _roll_rows(buf, shift_, impl_)
            d = batch_axis_name(mesh)
            bspec = P(None, d, *([None] * (buf.ndim - 2)))
            return shard_map_call(
                mesh, functools.partial(_roll_rows, impl=impl_),
                (bspec, P(d)), bspec, buf, shift_)

    new_caches = []
    for run in caches:
        sc = run["self"]
        S = sc["pos"].shape[-1]
        run_len, B = sc["pos"].shape[0], sc["pos"].shape[1]
        j = jnp.arange(S, dtype=jnp.int32)[None, :]
        start = (width - valid_len.astype(jnp.int32))[:, None]
        pos_row = jnp.where((j >= start) & (j < width), j - start, -1)
        new_sc = {"pos": jnp.broadcast_to(pos_row[None], (run_len, B, S))}
        if "table" in sc:
            # paged compaction (§13): gather pools to the dense logical
            # view, roll it exactly like the dense path, re-page through
            # the unchanged tables.  Only exclusively-owned tables reach
            # this path (the one-pass rollout's identity stripes) — CoW
            # sharing exists only behind the serving engine, whose
            # admission compacts densely before paging in.
            from repro.kernels.cache_slot_write.ops import paged_slot_write
            nb = sc["table"].shape[-1]
            bs = (sc["k"] if "k" in sc else sc["ckv"]).shape[-2]
            dense = _paged_run_gather(sc, impl)
            for name, buf in dense.items():
                rolled = _pad_to_blocks(roll(buf, shift, impl), nb, bs)
                new_sc[name] = paged_slot_write(sc[name], rolled,
                                                sc["table"], impl=impl)
            new_sc["table"] = sc["table"]
        else:
            for name in ("k", "v", "ckv", "krope"):
                if name in sc:
                    new_sc[name] = roll(sc[name], shift, impl)
        new_caches.append({"self": new_sc})
    if mesh is not None:
        from repro.distributed.mesh import constrain_caches
        new_caches = constrain_caches(cfg, new_caches, mesh)
    return new_caches


def supports_drafting(cfg: ModelConfig, model_kwargs=None) -> bool:
    """Whether the §9 draft-verify decode loop applies.

    A rejected draft token must leave no trace: attention trunks discard it
    by invalidating its cache slot (pos = -1) and overwriting on the next
    block, but recurrent blocks (mamba / rwkv) fold every forwarded token
    into a running state that cannot be rewound.  Modality extras are not
    threaded through the drafted host loop, so the gate matches slot
    serving's."""
    return supports_slot_serving(cfg, model_kwargs)


def pad_cache(cfg: ModelConfig, caches, extra: int):
    """Append ``extra`` empty slots to every cache buffer's sequence axis.

    The drafted decode loop writes a static (k + 1)-token block at the
    per-row write offset each macro-step, so its last step can touch up to
    ``draft_k`` slots beyond the final kept token; without headroom the
    dynamic_update_slice would clamp backwards onto live slots.  New slots
    carry pos == -1 (empty) and zero K/V — exactly what ``init_cache``
    would have allocated at the larger width.
    """
    if extra <= 0:
        return caches
    assert supports_cache_realign(cfg), "pad_cache needs attention trunks"
    new_caches = []
    for run in caches:
        sc = run["self"]
        if "table" in sc:
            new_caches.append({"self": _pad_paged_run(sc, extra)})
            continue
        new_sc = {"pos": jnp.pad(sc["pos"], ((0, 0), (0, 0), (0, extra)),
                                 constant_values=-1)}
        for name in ("k", "v", "ckv", "krope"):
            if name in sc:
                buf = sc[name]
                pad = [(0, 0)] * buf.ndim
                pad[-2] = (0, extra)
                new_sc[name] = jnp.pad(buf, pad)
        new_caches.append({"self": new_sc})
    return new_caches


def _pad_paged_run(sc, extra: int):
    """Paged ``pad_cache``: grow every row's logical width by ``extra``.

    The logical (``pos``) width grows by exactly ``extra`` — matching the
    dense path bit-for-bit — while the physical pool only moves in whole
    blocks: the block-rounding slack is consumed first, and any remainder
    appends fresh zero blocks to the pool tail and extends each table row
    with an identity stripe of them (exclusively owned — padding is only
    used by the fixed-batch drafted loop, never on CoW-shared serving
    rows)."""
    table = sc["table"]
    run_len, B, nb = table.shape
    pos = sc["pos"]
    S = pos.shape[-1]
    ref = sc["k"] if "k" in sc else sc["ckv"]
    bs = ref.shape[-2]
    nb_new = -(-(S + extra) // bs)
    add = nb_new - nb
    new_sc = {"pos": jnp.pad(pos, ((0, 0), (0, 0), (0, extra)),
                             constant_values=-1)}
    if add == 0:
        for name in ("k", "v", "ckv", "krope"):
            if name in sc:
                new_sc[name] = sc[name]
        new_sc["table"] = table
        return new_sc
    NB = ref.shape[1]
    fresh = (NB + jnp.arange(B * add, dtype=jnp.int32).reshape(B, add))
    new_sc["table"] = jnp.concatenate(
        [table, jnp.broadcast_to(fresh[None], (run_len, B, add))], axis=-1)
    for name in ("k", "v", "ckv", "krope"):
        if name in sc:
            buf = sc[name]
            pad = [(0, 0)] * buf.ndim
            pad[1] = (0, B * add)
            new_sc[name] = jnp.pad(buf, pad)
    return new_sc


def supports_slot_serving(cfg: ModelConfig, model_kwargs=None) -> bool:
    """Whether the continuous-batching slot engine (DESIGN.md §6) applies.

    Needs per-slot KV state (attention-only trunk, same constraint as cache
    realignment) and none of the modality extras the persistent decode batch
    does not carry (encoder memory / vision prefix)."""
    kw = model_kwargs or {}
    return (supports_cache_realign(cfg)
            and not cfg.encoder_layers
            and not cfg.num_prefix_embeddings
            and kw.get("encoder_out") is None
            and kw.get("prefix_embeds") is None)


def write_cache_slots(cfg: ModelConfig, dst_caches, src_caches, slots, *,
                      impl: str = "auto", mesh=None):
    """Admit prefilled rows into the persistent serving batch, in place.

    dst_caches: trunk caches over B slots; src_caches: same structure over R
    admitted rows (same sequence length); slots: (R,) int32 destination slot
    per source row.  Every leaf's row ``slots[i]`` along the batch axis is
    replaced by source row ``i`` via the cache_slot_write batched scatter
    (Pallas on TPU) on the flattened (run, batch[, head]) rows — the same
    layout cache_gather rolls.  Duplicate slots must carry identical rows
    (the admission path pads partial groups by duplicating a real row).

    pos arrays ride a plain jnp scatter (they are tiny and int32).
    Returns the updated cache pytree; untouched slots are bit-identical.

    Under a ``mesh`` with a KV-head-sharded cache the scatter runs inside a
    shard_map boundary over the head axis: slot indices are *batch* indices
    and therefore replicated, so each model shard rewrites its local head
    slice independently (DESIGN.md §8).
    """
    from repro.kernels.cache_slot_write.ops import cache_slot_write
    assert supports_cache_realign(cfg), "slot serving needs attention trunks"
    slots = slots.astype(jnp.int32)
    if any("table" in run["self"] for run in dst_caches):
        return _write_cache_slots_paged(dst_caches, src_caches, slots,
                                        impl=impl)

    def scatter(d, s, slots_):
        run_len, B = d.shape[0], d.shape[1]
        R = s.shape[1]
        per = 1                                      # heads folded after batch
        for sz in d.shape[2:-2]:
            per *= sz
        r0 = jnp.arange(run_len, dtype=jnp.int32)[:, None, None]
        h = jnp.arange(per, dtype=jnp.int32)[None, None, :]
        rows = ((r0 * B + slots_[None, :, None]) * per + h).reshape(-1)
        flat = cache_slot_write(
            d.reshape((run_len * B * per,) + d.shape[-2:]),
            s.reshape((run_len * R * per,) + s.shape[-2:]),
            rows, impl=impl)
        return flat.reshape(d.shape)

    new_caches = []
    for dst_run, src_run in zip(dst_caches, src_caches):
        dsc, ssc = dst_run["self"], src_run["self"]
        new_sc = {"pos": dsc["pos"].at[:, slots].set(ssc["pos"])}
        for name in ("k", "v", "ckv", "krope"):
            if name not in dsc:
                continue
            d, s = dsc[name], ssc[name]
            h_ax = None
            if mesh is not None and d.ndim == 5:
                from repro.distributed.shard_wrap import model_axis
                h_ax = model_axis(mesh, d.shape[2])
            if h_ax is not None:
                from repro.distributed.shard_wrap import shard_map_call
                from jax.sharding import PartitionSpec as P
                hspec = P(None, None, h_ax, None, None)
                new_sc[name] = shard_map_call(
                    mesh, scatter, (hspec, hspec, P()), hspec, d, s, slots)
            else:
                new_sc[name] = scatter(d, s, slots)
        new_caches.append({"self": new_sc})
    return new_caches


def _write_cache_slots_paged(dst_caches, src_caches, slots, *,
                             impl: str = "auto"):
    """Admit dense prefilled rows into a *paged* persistent cache (§13).

    The admission forward runs on small throwaway dense caches (identical
    device programs to the dense engine — that is what makes paged serving
    trivially token-identical); this scatter re-pages each admitted row
    into the blocks its table references via ``paged_slot_write``.  The
    addressed rows must be exclusively owned — the paged engine admits
    leaders with freshly allocated full-width tables and never routes
    CoW-sharing followers through here.

    A dense source narrower than the paged logical width is padded with
    empty slots (pos == -1); K/V is zero-padded to the block-rounded
    physical width so the scatter lands on whole blocks.
    """
    from repro.kernels.cache_slot_write.ops import paged_slot_write
    new_caches = []
    for dst_run, src_run in zip(dst_caches, src_caches):
        dsc, ssc = dst_run["self"], src_run["self"]
        S_paged = dsc["pos"].shape[-1]
        S_src = ssc["pos"].shape[-1]
        assert S_src <= S_paged, (S_src, S_paged)
        nb = dsc["table"].shape[-1]
        bs = (dsc["k"] if "k" in dsc else dsc["ckv"]).shape[-2]
        src_pos = ssc["pos"]
        if S_src < S_paged:
            src_pos = jnp.pad(src_pos, ((0, 0), (0, 0), (0, S_paged - S_src)),
                              constant_values=-1)
        new_sc = {"pos": dsc["pos"].at[:, slots].set(src_pos),
                  "table": dsc["table"]}
        table = dsc["table"][:, slots]               # (run, R, nb)
        for name in ("k", "v", "ckv", "krope"):
            if name not in dsc:
                continue
            new_sc[name] = paged_slot_write(
                dsc[name], _pad_to_blocks(ssc[name], nb, bs), table,
                impl=impl)
        new_caches.append({"self": new_sc})
    return new_caches


def prefill(params, cfg: ModelConfig, tokens, positions, caches, *,
            encoder_out=None, encoder_positions=None, prefix_embeds=None,
            use_pallas: bool = False):
    """Run the prompt through the model, filling caches at slots [0, T).

    Returns (logits (B, T, V), new_caches)."""
    OP_COUNTS["prefill"] += 1
    x = _embed(params, cfg, tokens, positions if prefix_embeds is None
               else positions[:, prefix_embeds.shape[1]:])
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    x, caches, _ = apply_trunk(params["trunk"], cfg, x, positions,
                               caches=caches, cache_start=0,
                               encoder_out=encoder_out,
                               encoder_positions=encoder_positions,
                               use_pallas=use_pallas)
    x = apply_rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if prefix_embeds is not None:
        x = x[:, prefix_embeds.shape[1]:]
    return _logits(params, cfg, x), caches


def decode_step(params, cfg: ModelConfig, token, position, caches, cache_start, *,
                encoder_out=None, encoder_positions=None,
                use_pallas: bool = False, kv_length=None, kv_start=None,
                mesh=None, with_aux: bool = False):
    """One decode step over a short token block.

    token: (B, T) with small T — 1 for classic decode, k + 1 for a §9
    draft-verify block; position: (B, T) (-1 marks done rows / draft
    padding); cache_start: first slot to write — scalar int32 (lockstep
    decode) or (B,) int32 per-row slots (serving slot scheduler / drafted
    loops, where each row sits at its own decode depth).  The T tokens are
    written at slots [cache_start, cache_start + T) before attending, so
    within-block causality is ordinary position masking.
    kv_length: optional per-row live cache extent (scalar or (B,) int32);
    attention beyond it is skipped by the flash-decode kernel.  Defaults to
    ``cache_start + T`` — the just-written block ends the live range.
    Multi-token blocks MUST thread it (the decode dispatch requires it,
    models/attention._decode_shaped).
    kv_start: optional per-row first live slot; pass only when the context
    is contiguous from that slot (left-padded prompt / compacted layout,
    no vision prefix) so the kernel can also skip the dead left padding.
    mesh: optional live Mesh — decode attention then runs inside the §8
    shard_map boundary (batch over data, KV heads over model).
    Returns (logits (B, 1, V), new_caches), and the trunk's aux outputs
    third with ``with_aux`` (``moe_held_tokens``: the step's routed
    assignments landing on held experts)."""
    OP_COUNTS["decode_step"] += 1
    x = _embed(params, cfg, token, position)
    x, caches, aux = apply_trunk(params["trunk"], cfg, x, position,
                                 caches=caches, cache_start=cache_start,
                                 encoder_out=encoder_out,
                                 encoder_positions=encoder_positions,
                                 use_pallas=use_pallas, kv_length=kv_length,
                                 kv_start=kv_start, mesh=mesh)
    x = apply_rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if with_aux:
        return _logits(params, cfg, x), caches, aux
    return _logits(params, cfg, x), caches
