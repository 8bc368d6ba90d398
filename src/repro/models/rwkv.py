"""RWKV6 ("Finch") block: data-dependent decay time-mix + channel-mix.

Per head (k-dim = v-dim = head_dim), with data-dependent per-channel decay
``w_t`` and bonus ``u``::

    y_t = r_t · (S_{t-1} + diag(u) k_t v_tᵀ)
    S_t = diag(w_t) S_{t-1} + k_t v_tᵀ

Token-shift uses the RWKV6 "ddlerp": a low-rank data-dependent interpolation
between x_t and x_{t-1} per projection stream.

Padding: embeddings at invalid positions are zeroed by the trunk, and k is
masked / w forced to 1 there, so the state is untouched by pads.

The time-mix recurrence runs as the jnp scan here (``wkv_scan``); the
Pallas kernel in `repro.kernels.rwkv6_wkv` is tested against it but not
called by the model.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import apply_dense, make_dense, split_keys

STREAMS = ("r", "k", "v", "w", "g")


def make_rwkv_time_mix(key, cfg: ModelConfig, dtype):
    d, rank = cfg.d_model, cfg.rwkv_lora_rank
    H, hd = cfg.rwkv_num_heads, cfg.rwkv_head_dim
    ks = split_keys(key, 12)
    p = {
        "mu_base": jnp.zeros((d,), dtype),
        "mu": jnp.zeros((len(STREAMS), d), dtype),
        "lora_a": make_dense(ks[0], d, len(STREAMS) * rank, False, dtype),
        "lora_b": (jax.random.normal(ks[1], (len(STREAMS), rank, d)) * 0.01).astype(dtype),
        "wr": make_dense(ks[2], d, d, False, dtype),
        "wk": make_dense(ks[3], d, d, False, dtype),
        "wv": make_dense(ks[4], d, d, False, dtype),
        "wg": make_dense(ks[5], d, d, False, dtype),
        "wo": make_dense(ks[6], d, d, False, dtype, scale=1.0 / math.sqrt(d)),
        # decay: w = exp(-exp(w0 + lora_w(x)))
        "w0": jnp.full((d,), -6.0, dtype),
        "w_lora_a": make_dense(ks[7], d, rank, False, dtype),
        "w_lora_b": (jax.random.normal(ks[8], (rank, d)) * 0.01).astype(dtype),
        "u": (jax.random.normal(ks[9], (d,)) * 0.1).astype(dtype),
        "ln_x_scale": jnp.ones((H, hd), dtype),
        "ln_x_bias": jnp.zeros((H, hd), dtype),
    }
    return p


def make_rwkv_channel_mix(key, cfg: ModelConfig, dtype):
    d, ff = cfg.d_model, cfg.d_ff
    ks = split_keys(key, 3)
    return {
        "mu_k": jnp.full((d,), 0.5, dtype),
        "mu_r": jnp.full((d,), 0.5, dtype),
        "wk": make_dense(ks[0], d, ff, False, dtype),
        "wv": make_dense(ks[1], ff, d, False, dtype, scale=1.0 / math.sqrt(ff)),
        "wr": make_dense(ks[2], d, d, False, dtype),
    }


def init_rwkv_cache(cfg: ModelConfig, batch: int, dtype):
    H, hd = cfg.rwkv_num_heads, cfg.rwkv_head_dim
    return {
        "shift_t": jnp.zeros((batch, cfg.d_model), dtype),
        "shift_c": jnp.zeros((batch, cfg.d_model), dtype),
        "wkv": jnp.zeros((batch, H, hd, hd), jnp.float32),
    }


def _token_shift(x, x_prev_row):
    """(B,T,d) -> previous-token tensor; first slot uses x_prev_row (B,d)."""
    return jnp.concatenate([x_prev_row[:, None, :], x[:, :-1, :]], axis=1)


def _ddlerp(p, x, xprev):
    """RWKV6 data-dependent token-shift for the 5 streams."""
    xx = xprev - x
    base = x + xx * p["mu_base"].astype(x.dtype)
    lora = jnp.tanh(apply_dense(p["lora_a"], base))
    B, T, _ = x.shape
    rank = p["lora_b"].shape[1]
    lora = lora.reshape(B, T, len(STREAMS), rank)
    dmu = jnp.einsum("btsr,srd->btsd", lora, p["lora_b"].astype(x.dtype))
    mixed = []
    for i, _ in enumerate(STREAMS):
        m = p["mu"][i].astype(x.dtype) + dmu[:, :, i, :]
        mixed.append(x + xx * m)
    return mixed  # list of (B,T,d) for r,k,v,w,g


def _group_norm(p, y, eps):
    """y: (B,T,H,hd) per-head layer norm."""
    mu = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.var(y, axis=-1, keepdims=True)
    yn = (y - mu) * jax.lax.rsqrt(var + eps)
    return yn * p["ln_x_scale"].astype(y.dtype) + p["ln_x_bias"].astype(y.dtype)


def wkv_scan(r, k, v, w, u, s0, chunk: int = 64):
    """Reference jnp recurrence.

    r,k,v,w: (B, T, H, hd) float32; u: (H, hd); s0: (B, H, hd, hd).
    Returns y (B,T,H,hd), s_final.  For long T the scan is chunked with
    rematerialisation so training residuals hold only chunk-boundary states
    (T x (B,H,hd,hd) otherwise).
    """
    T = r.shape[1]

    def step(s, inp):
        r_t, k_t, v_t, w_t = inp                                  # (B,H,hd)
        kv = k_t[..., :, None] * v_t[..., None, :]                # (B,H,hd,hd)
        y = jnp.einsum("bhk,bhkv->bhv", r_t, s + u[None, :, :, None] * kv)
        s = w_t[..., :, None] * s + kv
        return s, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))
    if T > chunk and T % chunk == 0:
        nch = T // chunk

        @jax.checkpoint
        def chunk_body(s, xs_c):
            return jax.lax.scan(step, s, xs_c)

        xs_c = jax.tree.map(lambda a: a.reshape(nch, chunk, *a.shape[1:]), xs)
        s_final, ys = jax.lax.scan(chunk_body, s0, xs_c)
        ys = ys.reshape(T, *ys.shape[2:])
    else:
        s_final, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1), s_final


def apply_rwkv_time_mix(p, cfg: ModelConfig, x, positions, *, cache=None):
    B, T, d = x.shape
    H, hd = cfg.rwkv_num_heads, cfg.rwkv_head_dim
    valid = (positions >= 0)[..., None].astype(jnp.float32)

    xprev_row = cache["shift_t"].astype(x.dtype) if cache is not None else \
        jnp.zeros((B, d), x.dtype)
    xprev = _token_shift(x, xprev_row)
    xr, xk, xv, xw, xg = _ddlerp(p, x, xprev)

    r = apply_dense(p["wr"], xr).astype(jnp.float32)
    k = apply_dense(p["wk"], xk).astype(jnp.float32) * valid
    v = apply_dense(p["wv"], xv).astype(jnp.float32)
    g = jax.nn.silu(apply_dense(p["wg"], xg))

    logw = p["w0"].astype(jnp.float32) + \
        (jnp.tanh(apply_dense(p["w_lora_a"], xw)).astype(jnp.float32)
         @ p["w_lora_b"].astype(jnp.float32))
    w = jnp.exp(-jnp.exp(logw))                                    # (B,T,d) in (0,1)
    w = jnp.where(valid > 0, w, 1.0)                               # pads: no decay

    shp = (B, T, H, hd)
    r_, k_, v_, w_ = (t.reshape(shp) for t in (r, k, v, w))
    u = p["u"].astype(jnp.float32).reshape(H, hd)
    s0 = cache["wkv"] if cache is not None else jnp.zeros((B, H, hd, hd), jnp.float32)

    y, s_final = wkv_scan(r_, k_, v_, w_, u, s0, cfg.scan_chunk)

    y = _group_norm(p, y.astype(x.dtype), cfg.norm_eps).reshape(B, T, d)
    out = apply_dense(p["wo"], y * g)

    new_cache = None
    if cache is not None:
        new_cache = {"shift_t": x[:, -1, :].astype(cache["shift_t"].dtype),
                     "wkv": s_final}
    return out, new_cache


def apply_rwkv_channel_mix(p, cfg: ModelConfig, x, positions, *, cache=None):
    B, T, d = x.shape
    xprev_row = cache["shift_c"].astype(x.dtype) if cache is not None else \
        jnp.zeros((B, d), x.dtype)
    xprev = _token_shift(x, xprev_row)
    xx = xprev - x
    xk = x + xx * p["mu_k"].astype(x.dtype)
    xr = x + xx * p["mu_r"].astype(x.dtype)
    k = jnp.square(jax.nn.relu(apply_dense(p["wk"], xk)))
    kv = apply_dense(p["wv"], k)
    out = jax.nn.sigmoid(apply_dense(p["wr"], xr)) * kv
    new_cache = None
    if cache is not None:
        new_cache = {"shift_c": x[:, -1, :].astype(cache["shift_c"].dtype)}
    return out, new_cache
