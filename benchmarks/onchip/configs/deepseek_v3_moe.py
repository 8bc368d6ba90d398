"""Plain reference of the DeepSeek-V3 decoder block (arXiv:2412.19437, as
published in Moonlight-16B-A3B's config and modelling code), written from
the published architecture and independent of the program under test.

Per layer: RMSNorm -> multi-head latent attention in its decompressed
textbook form (q = x Wq with no q compression; [c, k_pe] = x Wkv_a; c
RMS-normed; [k_nope, v] per head = c Wkv_b; rotary embedding on q_pe and
the shared k_pe; softmax scale (nope + rope)^-0.5; causal mask) ->
residual; RMSNorm -> feed-forward -> residual.  The first
``first_k_dense_replace`` layers take a dense SwiGLU FFN; the rest a MoE:
float32 sigmoid scores over all ``n_router_experts``, the top
``num_experts_per_tok`` chosen on score + ``e_score_correction_bias``
(noaux_tc, one group), weighted by their unbiased scores normalised over
the chosen (norm_topk_prob) and scaled by ``routed_scaling_factor``;
SwiGLU experts; the shared experts (one SwiGLU of n_shared x width) added.
A final RMSNorm and an untied output head.

The held share: the configuration holds ``n_routed_experts`` of the
router's experts (from ``first_held_expert``), as one chip of an
expert-parallel deployment does.  Only they contribute their routed part
of a MoE layer's output, in this reference as in the program; what the
other chips' experts would add is left out of both.

Two things live here:

* ``init_weights``: the benchmark's random weights, drawn from a seed in one
  jitted call on the device, in the served dtype, grouped per run of
  layers ("dense", "moe").  The program is handed these weights
  (re-laid-out by ``deepseek_v3_moe_program.py``); the reference draws them
  again from the same seed and takes nothing from the program.
* ``token_logprobs``: a teacher-forced forward over whole sequences that
  returns the log-probability of every token given its prefix (its logits
  are ``forward_logits``).  ``mode``
  "reference" computes in float32 with matmuls at ``highest`` precision;
  "control" is the served program one precision down: every linear
  layer's operands (the router and each expert among them) rounded to
  float8 e4m3, the next precision below the configuration's bfloat16,
  and every activation stored in bfloat16 as the program stores it (as
  float8 serving keeps its activations).  Rounding only the operands and
  keeping float32 activations (``qwen3_dense``'s control) reads little
  above the bf16 program here: bf16 already flips routing choices.

Departures from the published model: weights are random (normal, fan-in
scaled; the correction bias normal with std ``BIAS_STD``), the norm scales
drawn around 1, and where the configuration file gives an
``eos_logit_margin`` one residual lane holds EOS's logit that far below the
rest (``init_weights``).  The rotary embedding rotates halves (the first
rd/2 rope columns against the last rd/2); the published code rotates
interleaved pairs, which on random weights is a fixed permutation of the
rope columns of Wq and Wkv_a.
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
# one row of 256 random tokens at the published widths, 8 of 64 experts
# held: 0.627 over a dense and 3 MoE layers, 0.653 over a dense and 5)
RESID_VAR_PER_LAYER = 0.64
BIAS_STD = 0.1                 # e_score_correction_bias, drawn (assumed)


def sizes_of(config: dict, eos_margin: float = 0.0) -> tuple:
    """Hashable (static) sizes from a config dict, and the EOS margin of
    the weights (``init_weights``).  Refuses settings this reference does
    not model."""
    c = config
    need = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "n_group": 1, "topk_group": 1, "q_lora_rank": None,
            "moe_layer_freq": 1, "norm_topk_prob": True,
            "tie_word_embeddings": False}
    for k, v in need.items():
        if c[k] != v:
            raise ValueError(f"reference models {k}={v!r}, config has {c[k]!r}")
    return (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["intermediate_size"], c["moe_intermediate_size"],
            c["n_router_experts"], c["num_experts_per_tok"],
            c["n_shared_experts"], c["n_routed_experts"],
            c["first_held_expert"], c["first_k_dense_replace"],
            c["vocab_size"], float(c["rms_norm_eps"]), float(c["rope_theta"]),
            float(c["routed_scaling_factor"]), float(eos_margin))


def _shapes(sizes: tuple):
    """{run: {name: (shape, std)}} of the matrices, {run: {name: shape}} of
    the norm scales; runs "dense" and "moe" stack their layers."""
    (L, d, H, r, nd, rd, vd, ff, mff, E, k, ns, n_held, _, first,
     V, *_) = sizes
    runs = {"dense": first, "moe": L - first}
    mats, norms = {"": {"embed": ((V, d), 0.02),
                        "lm_head": ((d, V), d ** -0.5)}}, {"": {"final_norm": (d,)}}
    for run, n in runs.items():
        mats[run] = {
            "wq": ((n, d, H * (nd + rd)), d ** -0.5),
            "wkv_a": ((n, d, r + rd), d ** -0.5),
            "wkv_b": ((n, r, H * (nd + vd)), r ** -0.5),
            "wo": ((n, H * vd, d), (H * vd) ** -0.5),
        }
        norms[run] = {"attn_norm": (n, d), "mlp_norm": (n, d),
                      "kv_norm": (n, r)}
    mats["dense"].update({"w_gate": ((first, d, ff), d ** -0.5),
                          "w_up": ((first, d, ff), d ** -0.5),
                          "w_down": ((first, ff, d), ff ** -0.5)})
    n = L - first
    mats["moe"].update({
        "router": ((n, d, E), d ** -0.5),
        "e_score_correction_bias": ((n, E), BIAS_STD),
        "w_gate": ((n, n_held, d, mff), d ** -0.5),
        "w_up": ((n, n_held, d, mff), d ** -0.5),
        "w_down": ((n, n_held, mff, d), mff ** -0.5),
        "shared_gate": ((n, d, ns * mff), d ** -0.5),
        "shared_up": ((n, d, ns * mff), d ** -0.5),
        "shared_down": ((n, ns * mff, d), (ns * mff) ** -0.5),
    })
    return mats, norms


@functools.partial(jax.jit, static_argnums=(1,))
def init_weights(key, sizes: tuple):
    """Random weights in bfloat16: {"embed", "lm_head", "final_norm",
    "dense": {...}, "moe": {...}}, each run's leaves stacked over its
    layers.  Matrices are (in, out) with std 1/sqrt(in) (the embedding
    0.02); norm scales are 1 + 0.1 * normal.

    With an EOS margin m > 0 the last residual lane carries a constant:
    every token's embedding holds ``CARRIER`` there (EOS's holds -1), no
    layer writes to it (that lane of every output projection into the
    residual is 0: attention ``wo``, the dense and shared ``w_down`` and
    every expert's), and the final norm's gain there is sized so that the
    lane lowers EOS's logit by about m nats against every other token's.
    So a sampled row all but never ends early and every seed does the same
    work; an EOS written into a draft is still accepted and served."""
    L, d = sizes[0], sizes[1]
    eos_margin = sizes[-1]
    mats, norms = _shapes(sizes)
    names = sorted((run, n) for run in mats for n in mats[run]) + \
        sorted((run, n) for run in norms for n in norms[run])
    keys = dict(zip(names, jax.random.split(key, len(names))))
    w = {"dense": {}, "moe": {}}

    def put(run, n, a):
        (w[run] if run else w)[n] = a.astype(jnp.bfloat16)

    for run in mats:
        for n, (shape, std) in mats[run].items():
            put(run, n, jax.random.normal(keys[run, n], shape, jnp.float32)
                * std)
    for run in norms:
        for n, shape in norms[run].items():
            put(run, n, 1.0 + 0.1 * jax.random.normal(keys[run, n], shape,
                                                      jnp.float32))
    if eos_margin:
        k = d - 1
        gain = eos_margin * math.sqrt(RESID_VAR_PER_LAYER * L) / CARRIER
        w["embed"] = w["embed"].at[:, k].set(CARRIER).at[EOS_ID, k].set(-1.0)
        w["lm_head"] = w["lm_head"].at[k, :].set(CARRIER) \
            .at[k, EOS_ID].set(-1.0)
        for run in ("dense", "moe"):
            w[run]["wo"] = w[run]["wo"].at[..., k].set(0.0)
        w["dense"]["w_down"] = w["dense"]["w_down"].at[..., k].set(0.0)
        w["moe"]["w_down"] = w["moe"]["w_down"].at[..., k].set(0.0)
        w["moe"]["shared_down"] = w["moe"]["shared_down"].at[..., k].set(0.0)
        w["final_norm"] = w["final_norm"].at[k].set(gain)
    return w


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: (R, T, H, rd); pos: (T,).  Rotate-half layout."""
    half = x.shape[-1] // 2
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


def _act(x, control):
    """The control stores every activation in bfloat16, as the served
    program does; the reference keeps float32."""
    return x.astype(jnp.bfloat16).astype(jnp.float32) if control else x


def _linear(x, w, control):
    """x: (..., in) float32; w: (in, out) stored weight."""
    w = w.astype(jnp.float32)
    if control:
        x = _fp8(x, -1)
        w = _fp8(w, 0)
    return _act(x @ w, control)


def _experts(x, w, control):
    """x: (R, T, [E,] in); w: (E, in, out) -> (R, T, E, out)."""
    w = w.astype(jnp.float32)
    if control:
        x = _fp8(x, -1)
        w = _fp8(w, 1)
    spec = "rtd,edf->rtef" if x.ndim == 3 else "rted,edf->rtef"
    return _act(jnp.einsum(spec, x, w), control)


@functools.partial(jax.jit, static_argnums=(1, 4))
def token_logprobs(w, sizes: tuple, tokens, length, mode: str):
    """tokens: (R, T) int32, right-padded; length: (R,) valid tokens.

    Returns (R, T) float32: entry t is log p(tokens[t] | tokens[:t]) for
    1 <= t < length, and 0 elsewhere."""
    R, T = tokens.shape
    pos = jnp.arange(T, dtype=jnp.int32)
    logp = jax.nn.log_softmax(forward_logits(w, sizes, tokens, mode)[:, :-1],
                              axis=-1)
    lp = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    lp = jnp.concatenate([jnp.zeros((R, 1), jnp.float32), lp], axis=1)
    return jnp.where(pos[None, :] < length[:, None], lp, 0.0)


def forward_logits(w, sizes: tuple, tokens, mode: str):
    """tokens: (R, T) int32 -> (R, T, V) float32 causal logits."""
    (L, d, H, r, nd, rd, vd, ff, mff, E, k, ns, n_held, held0, first,
     V, eps, theta, scaling, _) = sizes
    control = mode == "control"
    R, T = tokens.shape
    pos = jnp.arange(T, dtype=jnp.int32)
    causal = pos[None, :] <= pos[:, None]                        # (Tq, Tk)

    def attention(x, p):
        h = _act(_rms(x, p["attn_norm"], eps), control)
        q = _linear(h, p["wq"], control).reshape(R, T, H, nd + rd)
        kv_a = _linear(h, p["wkv_a"], control)
        c = _act(_rms(kv_a[..., :r], p["kv_norm"], eps), control)
        k_pe = _rope(kv_a[..., r:][:, :, None, :], pos, theta)  # (R,T,1,rd)
        kv = _linear(c, p["wkv_b"], control).reshape(R, T, H, nd + vd)
        qf = jnp.concatenate([q[..., :nd], _rope(q[..., nd:], pos, theta)],
                             -1)
        kf = jnp.concatenate([kv[..., :nd],
                              jnp.broadcast_to(k_pe, (R, T, H, rd))], -1)
        s = jnp.einsum("rqhd,rkhd->rhqk", qf, kf) / math.sqrt(nd + rd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("rhqk,rkhd->rqhd", jax.nn.softmax(s, axis=-1),
                       kv[..., nd:])
        return _act(x + _linear(a.reshape(R, T, H * vd), p["wo"], control),
                    control)

    def swiglu(h, wg, wu, wd):
        m = jax.nn.silu(_linear(h, wg, control)) * _linear(h, wu, control)
        return _linear(m, wd, control)

    def dense_layer(x, p):
        x = attention(x, p)
        h = _act(_rms(x, p["mlp_norm"], eps), control)
        return _act(x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"]),
                    control), None

    def moe_layer(x, p):
        x = attention(x, p)
        h = _act(_rms(x, p["mlp_norm"], eps), control)
        scores = jax.nn.sigmoid(_linear(h, p["router"], control))  # (R,T,E)
        _, idx = jax.lax.top_k(
            scores + p["e_score_correction_bias"].astype(jnp.float32), k)
        wts = jnp.take_along_axis(scores, idx, axis=-1)
        wts = wts / (wts.sum(-1, keepdims=True) + 1e-20) * scaling
        # each held expert's gate: the weight of the choice that names it
        gate = jnp.einsum("rtke,rtk->rte",
                          jax.nn.one_hot(idx - held0, n_held), wts)
        hid = jax.nn.silu(_experts(h, p["w_gate"], control)) \
            * _experts(h, p["w_up"], control)                    # (R,T,E,f)
        routed = jnp.einsum("rted,rte->rtd",
                            _experts(hid, p["w_down"], control), gate)
        shared = swiglu(h, p["shared_gate"], p["shared_up"],
                        p["shared_down"])
        return _act(x + _act(routed + shared, control), control), None

    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens].astype(jnp.float32)
        if first:
            x, _ = jax.lax.scan(dense_layer, x, w["dense"])
        x, _ = jax.lax.scan(moe_layer, x, w["moe"])
        x = _rms(x, w["final_norm"], eps)
        return _linear(x, w["lm_head"], control)                 # (R, T, V)
