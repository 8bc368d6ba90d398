"""Feed-forward layers: dense SwiGLU FFN and mixture-of-experts.

Three MoE execution strategies:

``dense``     every expert runs on every token, outputs combined with router
              weights.  Exact (no token dropping); used for smoke tests and
              small models.
``dispatch``  GShard-style grouped one-hot dispatch einsum with a capacity
              limit.  The battle-tested TPU formulation: tokens stay sharded
              on (pod, data), experts shard on `model` (expert parallelism),
              and the dispatch einsums carry the all-to-all.  Production
              default — EXPERIMENTS.md §Perf round 4 shows why.
``sort``      argsort-by-expert gather/scatter.  FLOP-honest (no dispatch
              matmuls) but GSPMD cannot shard the scatter (it replicates the
              token buffer) — kept for single-device use and as the
              measured-and-refuted §Perf round-4 hypothesis; the TPU fix is
              megablox/ragged kernels.

The layer may hold only some of the routed experts (``cfg.held_expert_start``
/ ``num_held_experts``, expert parallelism, DESIGN.md §15): the router still
scores all ``num_experts``, and ``dense`` computes the held experts' part of
the result for the tokens routed to them, dropping none.
The router is softmax top-k, or DeepSeek-V3's sigmoid scores chosen on
score plus a correction bias (``cfg.router_score``).

Aux losses (load-balance + router z-loss) are returned to the caller and
added to the RL/pretrain objective; ``moe_held_tokens`` counts the routed
assignments of valid tokens that land on held experts (blocks.py sums it
over layers).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import activation, apply_dense, make_dense, split_keys


# ------------------------------------------------------------------ dense FFN


def make_ffn(key, d: int, ff: int, dtype, kind: str = "swiglu"):
    ks = split_keys(key, 3)
    p = {
        "w_up": make_dense(ks[1], d, ff, False, dtype),
        "w_down": make_dense(ks[2], ff, d, False, dtype, scale=1.0 / math.sqrt(ff)),
    }
    if kind == "swiglu":
        p["w_gate"] = make_dense(ks[0], d, ff, False, dtype)
    return p


def apply_ffn(p, x, act_name: str = "silu"):
    act = activation(act_name)
    if "w_gate" in p:   # swiglu
        return apply_dense(p["w_down"],
                           act(apply_dense(p["w_gate"], x)) * apply_dense(p["w_up"], x))
    return apply_dense(p["w_down"], act(apply_dense(p["w_up"], x)))


# ------------------------------------------------------------------ MoE


def make_moe(key, cfg: ModelConfig, dtype):
    E, d, ff = cfg.num_experts, cfg.d_model, cfg.resolved_moe_d_ff
    ks = split_keys(key, 5)

    def stack(k, ins, outs, scale=None):
        keys = jax.random.split(k, cfg.held_experts)
        return jnp.stack([make_dense(kk, ins, outs, False, dtype, scale)["kernel"]
                          for kk in keys])

    p = {
        "router": make_dense(ks[0], d, E, False, dtype),
        "w_gate": stack(ks[1], d, ff),
        "w_up": stack(ks[2], d, ff),
        "w_down": stack(ks[3], ff, d, 1.0 / math.sqrt(ff)),
    }
    if cfg.router_score == "sigmoid":
        p["e_score_correction_bias"] = jnp.zeros((E,), dtype)
    if cfg.num_shared_experts:
        p["shared"] = make_ffn(ks[4], d, ff * cfg.num_shared_experts, dtype)
    return p


def _sigmoid_router(p, cfg: ModelConfig, xf):
    """DeepSeek-V3 routing (noaux_tc, one group): float32 sigmoid scores;
    the top k of score + ``e_score_correction_bias`` are chosen and weighted
    by their unbiased scores, normalised over the k (norm_topk_prob).
    Returns (logits, probs for the aux losses, weights, idx)."""
    logits = jnp.matmul(xf.astype(jnp.float32),
                        p["router"]["kernel"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    choice = scores + p["e_score_correction_bias"].astype(jnp.float32)
    _, idx = jax.lax.top_k(choice, cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return logits, scores / scores.sum(-1, keepdims=True), weights, idx


def _router(p, cfg: ModelConfig, xf):
    """xf: (N, d) -> (weights (N,k), idx (N,k), aux dict)."""
    if cfg.router_score == "sigmoid":
        logits, probs, weights, idx = _sigmoid_router(p, cfg, xf)
    else:
        logits = (xf @ p["router"]["kernel"].astype(xf.dtype)
                  ).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scaling != 1.0:
        weights = weights * cfg.routed_scaling

    # Switch-style load-balance loss + z-loss.
    E = cfg.num_experts
    me = jnp.mean(probs, axis=0)                                   # (E,)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)             # (N,k,E)
    ce = jnp.mean(onehot.sum(1), axis=0) / cfg.num_experts_per_tok  # frac routed
    lb = E * jnp.sum(me * ce)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = {"moe_lb_loss": lb, "moe_z_loss": z,
           "moe_expert_frac": ce}
    return weights, idx, aux


def _experts_batched(p, xe, act_name):
    """xe: (E, C, d) -> (E, C, d) through per-expert SwiGLU."""
    act = activation(act_name)
    h = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(xe.dtype))
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(xe.dtype))
    return jnp.einsum("ecf,efd->ecd", act(h) * u, p["w_down"].astype(xe.dtype))


def _held_index(cfg: ModelConfig, idx):
    """Routed expert ids -> ids among the held experts; an expert that is
    not held falls outside [0, held_experts)."""
    return idx - cfg.held_expert_start if cfg.held_expert_start else idx


def _apply_moe_dense(p, cfg: ModelConfig, x):
    B, T, d = x.shape
    xf = x.reshape(-1, d)
    weights, idx, aux = _router(p, cfg, xf)
    act = activation(cfg.act)
    # all held experts on all tokens: (E, N, d)
    h = jnp.einsum("nd,edf->enf", xf, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("nd,edf->enf", xf, p["w_up"].astype(x.dtype))
    ye = jnp.einsum("enf,efd->end", act(h) * u, p["w_down"].astype(x.dtype))
    # (N,k,E); a routed expert that is not held has an all-zero row
    onehot = jax.nn.one_hot(_held_index(cfg, idx), cfg.held_experts,
                            dtype=x.dtype)
    combine = jnp.einsum("nke,nk->en", onehot, weights.astype(x.dtype))
    y = jnp.einsum("end,en->nd", ye, combine)
    return y.reshape(B, T, d), (idx, aux)


def _apply_moe_dispatch(p, cfg: ModelConfig, x):
    """GShard grouped dispatch.  Groups = batch rows (tokens of one sequence)."""
    B, T, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    xf = x.reshape(-1, d)
    weights, idx, aux = _router(p, cfg, xf)

    G = cfg.moe_groups or B                # default: one group per sequence
    G = min(G, B * T)
    while (B * T) % G:
        G -= 1
    n = (B * T) // G                       # tokens per group
    cap = max(1, int(math.ceil(k * n / E * cfg.capacity_factor)))
    cap = min(cap, k * n)
    idx_g = idx.reshape(G, n, k)
    w_g = weights.reshape(G, n, k).astype(x.dtype)
    x_g = xf.reshape(G, n, d)

    onehot = jax.nn.one_hot(idx_g, E, dtype=jnp.int32)             # (G,n,k,E)
    flat = onehot.reshape(G, n * k, E)
    pos = jnp.cumsum(flat, axis=1) - flat                          # rank within expert
    pos = pos.reshape(G, n, k, E)
    in_cap = pos < cap
    disp = (onehot * in_cap).astype(x.dtype)                       # keep mask
    pos_oh = jax.nn.one_hot(jnp.sum(pos * onehot, -1), cap, dtype=x.dtype)  # (G,n,k,cap)
    # dispatch tensor (G, n, k, E, cap) contracted immediately
    dispatch = jnp.einsum("gnke,gnkc->gnkec", disp, pos_oh)
    xe = jnp.einsum("gnkec,gnd->gecd", dispatch, x_g)              # (G,E,cap,d)
    ye = jax.vmap(lambda xg: _experts_batched(p, xg, cfg.act))(xe)  # (G,E,cap,d)
    combine = jnp.einsum("gnkec,gnk->gnkec", dispatch, w_g)
    y = jnp.einsum("gnkec,gecd->gnd", combine, ye)
    dropped = 1.0 - jnp.mean(jnp.sum(disp, axis=(2, 3)) > 0)
    aux["moe_drop_frac"] = dropped.astype(jnp.float32)
    return y.reshape(B, T, d), (idx, aux)


def _apply_moe_sort(p, cfg: ModelConfig, x):
    """Sort-based dispatch: argsort tokens by expert, scatter into a
    (E, cap, d) buffer, batched expert matmuls, gather back.

    Unlike the GShard one-hot einsum this moves data with gather/scatter
    instead of matmuls, so HLO FLOPs ≈ active expert FLOPs (the dispatch
    einsum inflates compute by up to 10x at deepseek-v3 scale — §Perf).
    """
    B, T, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    xf = x.reshape(-1, d)
    N = xf.shape[0]
    weights, idx, aux = _router(p, cfg, xf)

    cap = max(1, int(math.ceil(k * N / E * cfg.capacity_factor)))
    cap = min(cap, k * N)
    eid = idx.reshape(-1)                                  # (N*k,)
    tok = jnp.arange(N * k, dtype=jnp.int32) // k
    order = jnp.argsort(eid, stable=True)
    eid_s, tok_s = eid[order], tok[order]
    counts = jnp.bincount(eid, length=E)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(N * k, dtype=jnp.int32) - starts[eid_s]
    keep = rank < cap
    rank_c = jnp.minimum(rank, cap - 1)

    buf = jnp.zeros((E, cap, d), x.dtype)
    buf = buf.at[eid_s, rank_c].set(
        jnp.where(keep[:, None], xf[tok_s], 0.0), mode="drop")
    ye = _experts_batched(p, buf, cfg.act)                 # (E, cap, d)
    rows = ye[eid_s, rank_c] * keep[:, None].astype(x.dtype)
    w_s = weights.reshape(-1)[order].astype(x.dtype)
    y = jnp.zeros((N, d), x.dtype).at[tok_s].add(rows * w_s[:, None])
    aux["moe_drop_frac"] = (1.0 - keep.mean().astype(jnp.float32))
    return y.reshape(B, T, d), (idx, aux)


def apply_moe(p, cfg: ModelConfig, x, valid=None):
    """x: (B, T, d); valid: optional (B, T) bool, the tokens that count in
    ``moe_held_tokens`` (padding and finished rows do not)."""
    if cfg.moe_impl == "dispatch":
        y, (idx, aux) = _apply_moe_dispatch(p, cfg, x)
    elif cfg.moe_impl == "sort":
        y, (idx, aux) = _apply_moe_sort(p, cfg, x)
    else:
        y, (idx, aux) = _apply_moe_dense(p, cfg, x)
    e = _held_index(cfg, idx)
    held = (e >= 0) & (e < cfg.held_experts)
    if valid is not None:
        held &= valid.reshape(-1, 1)
    aux["moe_held_tokens"] = jnp.sum(held, dtype=jnp.int32)
    if "shared" in p:
        y = y + apply_ffn(p["shared"], x, cfg.act)
    return y, aux
