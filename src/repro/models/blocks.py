"""Decoder blocks and the layer-stack assembler.

A block is (kind, is_moe, cross) where kind ∈ {attn, mamba, rwkv}.  Layers
with identical signatures are *stacked* and executed with ``jax.lax.scan`` so
the lowered HLO stays small even for 88-layer trunks; heterogeneous trunks
(jamba) become a short python loop over signature runs, each run scanned.

Caches mirror the run structure: ``cache[run_idx]`` is a pytree whose leaves
have a leading ``run_len`` axis.  A run's cache stack is part of the layer
scan's *carry*: layer ``l`` writes its slot of the stack in place and the
flash-decode kernel reads it there, so a decode step moves one token's K/V
per layer, never a whole layer slice or the whole stack (DESIGN.md §3).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import apply_attention, init_kv_cache, make_attention
from .config import ATTN, MAMBA, RWKV, ModelConfig
from .layers import (apply_layernorm, apply_rmsnorm, make_layernorm,
                     make_rmsnorm, split_keys)
from .mamba import apply_mamba, init_mamba_cache, make_mamba
from .moe import apply_moe, apply_ffn, make_ffn, make_moe
from .rwkv import (apply_rwkv_channel_mix, apply_rwkv_time_mix,
                   init_rwkv_cache, make_rwkv_channel_mix, make_rwkv_time_mix)

BlockSig = Tuple[str, bool, bool]  # (kind, is_moe, cross_attention)


def block_signatures(cfg: ModelConfig) -> List[BlockSig]:
    return [(kind, moe, cfg.cross_attention)
            for kind, moe in cfg.layer_plan()]


def signature_runs(cfg: ModelConfig) -> List[Tuple[BlockSig, int]]:
    """Consecutive runs of identical block signatures: [(sig, run_len), ...]."""
    runs: List[Tuple[BlockSig, int]] = []
    for sig in block_signatures(cfg):
        if runs and runs[-1][0] == sig:
            runs[-1] = (sig, runs[-1][1] + 1)
        else:
            runs.append((sig, 1))
    return runs


# ------------------------------------------------------------------ single block


def make_block(key, cfg: ModelConfig, sig: BlockSig, dtype):
    kind, is_moe, cross = sig
    ks = split_keys(key, 6)
    norm = make_layernorm if kind == RWKV else make_rmsnorm
    p: Dict[str, Any] = {"norm1": norm(cfg.d_model, dtype),
                         "norm2": norm(cfg.d_model, dtype)}
    if kind == ATTN:
        p["attn"] = make_attention(ks[0], cfg, dtype)
    elif kind == MAMBA:
        p["mamba"] = make_mamba(ks[0], cfg, dtype)
    elif kind == RWKV:
        p["time_mix"] = make_rwkv_time_mix(ks[0], cfg, dtype)
    if cross:
        p["norm_ca"] = norm(cfg.d_model, dtype)
        p["cross_attn"] = make_attention(ks[1], cfg.replace(qk_norm=False), dtype)
    if kind == RWKV:
        p["channel_mix"] = make_rwkv_channel_mix(ks[2], cfg, dtype)
    elif is_moe:
        p["moe"] = make_moe(ks[2], cfg, dtype)
    else:
        p["mlp"] = make_ffn(ks[2], cfg.d_model, cfg.d_ff, dtype, cfg.ffn_kind)
    return p


def init_block_cache(cfg: ModelConfig, sig: BlockSig, batch: int, max_len: int, dtype):
    kind, _, cross = sig
    cache: Dict[str, Any] = {}
    if kind == ATTN:
        cache["self"] = init_kv_cache(cfg, batch, max_len, dtype)
    elif kind == MAMBA:
        cache["mamba"] = init_mamba_cache(cfg, batch, dtype)
    elif kind == RWKV:
        cache["rwkv"] = init_rwkv_cache(cfg, batch, dtype)
    # cross-attn K/V are recomputed from encoder_out each call (cheap for the
    # stubbed frontend lengths) — no cross cache entries needed.
    return cache


def _layer_state(stack, layer):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        stack)


def _write_state(stack, new, layer):
    return {k: jax.lax.dynamic_update_index_in_dim(
        a, new[k].astype(a.dtype), layer, 0) if k in new else a
        for k, a in stack.items()}


def apply_block(p, cfg: ModelConfig, sig: BlockSig, x, positions, *,
                cache=None, layer=None, cache_start=None, encoder_out=None,
                encoder_positions=None, causal: bool = True,
                kv_length=None, kv_start=None, mesh=None):
    """One block.  ``cache`` is the run's stacked cache, read and written at
    ``layer``: attention K/V in place, a recurrent layer's small state as
    one slice written back.  Returns (x, new stacked cache, aux)."""
    kind, is_moe, cross = sig
    norm = apply_layernorm if kind == RWKV else functools.partial(
        apply_rmsnorm, eps=cfg.norm_eps)
    aux: Dict[str, jnp.ndarray] = {}
    new_cache: Dict[str, Any] = {}

    h = norm(p["norm1"], x)
    if kind == ATTN:
        out, c = apply_attention(p["attn"], cfg, h, positions,
                                 cache=None if cache is None else cache["self"],
                                 layer=layer, cache_start=cache_start,
                                 causal=causal, kv_length=kv_length,
                                 kv_start=kv_start, mesh=mesh)
        if c is not None:
            new_cache["self"] = c
    elif kind == MAMBA:
        out, c = apply_mamba(p["mamba"], cfg, h, positions,
                             cache=None if cache is None else
                             _layer_state(cache["mamba"], layer))
        if c is not None:
            new_cache["mamba"] = _write_state(cache["mamba"], c, layer)
    else:  # RWKV time mix
        rwkv = None if cache is None else _layer_state(cache["rwkv"], layer)
        out, c = apply_rwkv_time_mix(p["time_mix"], cfg, h, positions,
                                     cache=rwkv)
        if c is not None:
            new_cache["rwkv"] = _write_state(cache["rwkv"], c, layer)
    x = x + out

    if cross:
        h = norm(p["norm_ca"], x)
        out, _ = apply_attention(p["cross_attn"], cfg, h, positions,
                                 kv_x=encoder_out, kv_positions=encoder_positions,
                                 causal=False)
        x = x + out

    h = norm(p["norm2"], x)
    if kind == RWKV:
        out, c = apply_rwkv_channel_mix(p["channel_mix"], cfg, h, positions,
                                        cache=rwkv)
        if c is not None:
            new_cache["rwkv"] = _write_state(new_cache["rwkv"], c, layer)
    elif is_moe:
        out, moe_aux = apply_moe(p["moe"], cfg, h, valid=positions >= 0)
        aux.update(moe_aux)
    else:
        out = apply_ffn(p["mlp"], h, cfg.act)
    x = x + out
    return x, (new_cache if cache is not None else None), aux


# ------------------------------------------------------------------ layer stack


def make_trunk(key, cfg: ModelConfig, dtype):
    """Returns params: list (one entry per run) of stacked block params."""
    runs = signature_runs(cfg)
    keys = split_keys(key, len(runs))
    trunk = []
    for (sig, run_len), k in zip(runs, keys):
        layer_keys = split_keys(k, run_len)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[make_block(lk, cfg, sig, dtype) for lk in layer_keys])
        trunk.append(stacked)
    return trunk


def init_trunk_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    caches = []
    for sig, run_len in signature_runs(cfg):
        one = init_block_cache(cfg, sig, batch, max_len, dtype)
        caches.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (run_len,) + x.shape).copy(), one))
    return caches


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


# aux outputs that are counts: summed over layers, not averaged
SUMMED_AUX = ("moe_held_tokens",)


def apply_trunk(trunk_params, cfg: ModelConfig, x, positions, *,
                caches=None, cache_start=None, encoder_out=None,
                encoder_positions=None, causal: bool = True,
                kv_length=None, kv_start=None, mesh=None):
    """Run all layers.  Returns (x, new_caches, aux): each aux output's
    mean over the layers that give it, a count (``SUMMED_AUX``) its sum.

    With ``caches``, each run's cache stack rides in the scan's carry and
    the scanned inputs are (params, layer index): every layer writes and
    reads its own slot of the stack in place, and no cache is emitted as
    scan output (which would copy each layer's slice into a fresh stack)."""
    runs = signature_runs(cfg)
    new_caches = [] if caches is not None else None
    aux_sums: Dict[str, jnp.ndarray] = {}
    aux_counts: Dict[str, int] = {}
    block_kw = dict(cache_start=cache_start, encoder_out=encoder_out,
                    encoder_positions=encoder_positions, causal=causal,
                    kv_length=kv_length, kv_start=kv_start, mesh=mesh)

    for run_idx, (sig, run_len) in enumerate(runs):
        params = trunk_params[run_idx]
        if caches is None:
            def body(h, layer_p):
                h, _, aux = apply_block(layer_p, cfg, sig, h, positions,
                                        **block_kw)
                return h, aux

            x, auxs = jax.lax.scan(_maybe_remat(body, cfg), x, params)
        else:
            # cached calls are never differentiated: no remat
            def body(carry, xs):
                h, stack = carry
                layer_p, layer = xs
                h, stack, aux = apply_block(layer_p, cfg, sig, h, positions,
                                            cache=stack, layer=layer,
                                            **block_kw)
                return (h, stack), aux

            (x, stack), auxs = jax.lax.scan(
                body, (x, caches[run_idx]),
                (params, jnp.arange(run_len, dtype=jnp.int32)))
            new_caches.append(stack)
        for k, v in auxs.items():           # v: (run_len, ...) from scan ys
            aux_sums[k] = aux_sums.get(k, 0) + jnp.sum(v, axis=0)
            aux_counts[k] = aux_counts.get(k, 0) + run_len

    aux = {k: aux_sums[k] if k in SUMMED_AUX else aux_sums[k] / aux_counts[k]
           for k in aux_sums}
    return x, new_caches, aux
