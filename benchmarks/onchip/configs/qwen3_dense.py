"""Plain reference of the Qwen3 dense decoder (arXiv:2505.09388), written
from the published architecture and independent of the program under test.

Per layer: RMSNorm -> GQA attention with a per-head RMSNorm on q and k
(qk-norm), rotary embeddings (rotate-half, base ``rope_theta``) and a causal
mask -> residual; RMSNorm -> SwiGLU MLP -> residual.  A final RMSNorm, and
logits against the embedding matrix where ``tie_word_embeddings`` holds.

Two things live here:

* ``init_weights``: the benchmark's random weights, drawn from a seed in one
  jitted call on the device, in the served dtype.  The program is handed
  these weights (re-laid-out by ``qwen3_dense_program.py``); the reference
  draws them again from the same seed and takes nothing from the program.
* ``token_logprobs``: a teacher-forced forward over whole sequences that
  returns the log-probability of every token given its prefix.  ``mode``
  "reference" computes in float32 with matmuls at ``highest`` precision;
  "control" is the same forward with every linear layer's operands rounded
  to float8 e4m3 (per-channel weight scales, per-token activation scales),
  the next precision below the configuration's bfloat16.

Departures from the published model: none in the mathematics; weights are
random (normal, fan-in scaled), the norm scales are drawn around 1, and
where the configuration file gives an ``eos_logit_margin`` one residual lane
holds EOS's logit that far below the rest (``init_weights``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


EOS_ID = 2                     # end of sequence, as the tokenizer numbers it
CARRIER = 2.0 ** -10           # the constant the last residual lane carries
# mean square a residual lane gains per layer under these weights (float32,
# one row of 256 random tokens: 0.76 at qwen3-0.6b's widths over 8 layers,
# 0.68 at qwen3-1.7b's over 6)
RESID_VAR_PER_LAYER = 0.72


def sizes_of(config: dict, eos_margin: float = 0.0) -> tuple:
    """Hashable (static) sizes from a published config dict, and the EOS
    margin of the weights (``init_weights``)."""
    c = config
    return (c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"], float(c["rms_norm_eps"]), float(c["rope_theta"]),
            bool(c["tie_word_embeddings"]), float(eos_margin))


@functools.partial(jax.jit, static_argnums=(1,))
def init_weights(key, sizes: tuple):
    """Random weights in bfloat16, stacked over layers.

    Matrices are (in, out) with std 1/sqrt(in); the embedding has std 0.02;
    norm scales are 1 + 0.1 * normal.

    With an EOS margin m > 0 the last residual lane carries a constant: every
    token's embedding holds ``CARRIER`` there (EOS's holds -1), no layer
    writes to it (that lane of each attention and MLP output projection is
    0), and the final norm's gain there is sized so that the lane lowers
    EOS's logit by about m nats against every other token's, at any
    position.  So a sampled row all but never ends early (e^-m of the
    chance it has under plain random weights) and every seed does the same
    work; an EOS written into a draft is still accepted and served."""
    L, d, H, Hkv, hd, ff, V, _, _, tied, eos_margin = sizes
    shapes = {
        "embed": ((V, d), 0.02),
        "wq": ((L, d, H * hd), d ** -0.5),
        "wk": ((L, d, Hkv * hd), d ** -0.5),
        "wv": ((L, d, Hkv * hd), d ** -0.5),
        "wo": ((L, H * hd, d), (H * hd) ** -0.5),
        "w_gate": ((L, d, ff), d ** -0.5),
        "w_up": ((L, d, ff), d ** -0.5),
        "w_down": ((L, ff, d), ff ** -0.5),
    }
    if not tied:
        shapes["lm_head"] = ((d, V), d ** -0.5)
    norms = {"attn_norm": (L, d), "mlp_norm": (L, d), "q_norm": (L, hd),
             "k_norm": (L, hd), "final_norm": (d,)}
    names = sorted(shapes) + sorted(norms)
    keys = dict(zip(names, jax.random.split(key, len(names))))
    w = {}
    for n, (shape, std) in shapes.items():
        w[n] = (jax.random.normal(keys[n], shape, jnp.float32)
                * std).astype(jnp.bfloat16)
    for n, shape in norms.items():
        w[n] = (1.0 + 0.1 * jax.random.normal(keys[n], shape, jnp.float32)
                ).astype(jnp.bfloat16)
    if eos_margin:
        k = d - 1
        # the lane reaches the final norm as CARRIER over the residual's rms,
        # about sqrt(RESID_VAR_PER_LAYER * L)
        gain = eos_margin * math.sqrt(RESID_VAR_PER_LAYER * L) / CARRIER
        w["embed"] = w["embed"].at[:, k].set(CARRIER).at[EOS_ID, k].set(-1.0)
        if not tied:
            w["lm_head"] = w["lm_head"].at[k, :].set(CARRIER) \
                .at[k, EOS_ID].set(-1.0)
        w["wo"] = w["wo"].at[:, :, k].set(0.0)
        w["w_down"] = w["w_down"].at[:, :, k].set(0.0)
        w["final_norm"] = w["final_norm"].at[k].set(gain)
    return w


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    """x: (R, T, H, hd); pos: (T,)."""
    hd = x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _linear(x, w, control):
    """x: (..., in) float32; w: (in, out) stored weight."""
    w = w.astype(jnp.float32)
    if control:
        x = _fp8(x, -1)
        w = _fp8(w, 0)
    return x @ w


@functools.partial(jax.jit, static_argnums=(1, 4))
def token_logprobs(w, sizes: tuple, tokens, length, mode: str):
    """tokens: (R, T) int32, right-padded; length: (R,) valid tokens.

    Returns (R, T) float32: entry t is log p(tokens[t] | tokens[:t]) for
    1 <= t < length, and 0 elsewhere."""
    L, d, H, Hkv, hd, ff, V, eps, theta, tied, _ = sizes
    control = mode == "control"
    R, T = tokens.shape
    pos = jnp.arange(T, dtype=jnp.int32)
    causal = pos[None, :] <= pos[:, None]                        # (Tq, Tk)
    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens].astype(jnp.float32)

        def layer(x, p):
            h = _rms(x, p["attn_norm"].astype(jnp.float32), eps)
            q = _linear(h, p["wq"], control).reshape(R, T, H, hd)
            k = _linear(h, p["wk"], control).reshape(R, T, Hkv, hd)
            v = _linear(h, p["wv"], control).reshape(R, T, Hkv, hd)
            q = _rope(_rms(q, p["q_norm"].astype(jnp.float32), eps), pos, theta)
            k = _rope(_rms(k, p["k_norm"].astype(jnp.float32), eps), pos, theta)
            g = H // Hkv
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
            s = jnp.einsum("rqhd,rkhd->rhqk", q, k) / math.sqrt(hd)
            s = jnp.where(causal[None, None], s, -jnp.inf)
            a = jnp.einsum("rhqk,rkhd->rqhd", jax.nn.softmax(s, axis=-1), v)
            x = x + _linear(a.reshape(R, T, H * hd), p["wo"], control)
            h = _rms(x, p["mlp_norm"].astype(jnp.float32), eps)
            m = jax.nn.silu(_linear(h, p["w_gate"], control)) \
                * _linear(h, p["w_up"], control)
            return x + _linear(m, p["w_down"], control), None

        layers = {k: w[k] for k in ("attn_norm", "mlp_norm", "wq", "wk", "wv",
                                    "wo", "q_norm", "k_norm", "w_gate",
                                    "w_up", "w_down")}
        x, _ = jax.lax.scan(layer, x, layers)
        x = _rms(x, w["final_norm"].astype(jnp.float32), eps)
        head = w["embed"].T if tied else w["lm_head"]
        logits = _linear(x, head, control)                       # (R, T, V)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    lp = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    lp = jnp.concatenate([jnp.zeros((R, 1), jnp.float32), lp], axis=1)
    return jnp.where(pos[None, :] < length[:, None], lp, 0.0)
