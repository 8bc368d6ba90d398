#!/usr/bin/env python3
"""Smoke test of SPEC-RL's main paths on a TPU, at the full published width
of qwen3-0.6b (28 layers, d=1024, GQA 16/8, vocab 151936, bfloat16) with
random weights.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the (data, model) = 2x2 mesh paths

One chip runs, in one process and in this order:

  kernels  the Pallas kernels of the main path against their jnp
           references on the same inputs: decode attention (dense and
           paged, T=1 and a T=k+1 draft block) against the ``blocked``
           path, and the cache-surgery kernels (roll, paged gather, slot
           write) and spec_verify against their references, exactly;
  fp32     the bf16 model's first-step log-softmax against the same params
           cast to float32 (matmuls at ``highest`` precision);
  train    three GRPO steps of the ``--variant spec`` trainer, built as
           ``python -m repro.launch.train`` builds it, on a problem set no
           larger than a batch, so steps 1 and 2 revisit their prompts and
           run verify -> cache_roll compaction -> resume;
  serve    ``python -m repro.launch.serve`` at full width, once with
           ``--spec-prefix --draft 4`` and once with ``--cache-layout
           paged``: every request finishes, and no quarantine, retry or
           decode-impl fallback happens.

``--chips 4`` runs only the mesh phases: two train steps and a
``MeshSlotServer`` serve on a 2x2 mesh, each checked for params and caches
that span the four devices and for sampled tokens whose logprobs agree with
a teacher-forced single-device scoring of the same tokens.

Each phase prints its own lines.  Any failure raises (exit code 1); no
phase is caught and continued.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or without the ``src/repro`` package beside this script, it
exits with code 2 before any work.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")
ARCH = "qwen3-0.6b"

# --- tolerances ------------------------------------------------------------
# Decode attention, Pallas against ``blocked`` (unit-normal q, k, v).  Both
# read the same bfloat16 cache and accumulate in float32; they differ in
# summation order and because XLA's default TPU matmul precision rounds the
# blocked path's float32 softmax weights to bfloat16 before p @ v (relative
# error 2^-9).  With |v| <= ~5 that is <= 0.01; a wrong mask, split or block
# redirect errs by O(1).
ATTN_TOL = 3e-2
# First-step log-softmax, bf16 model against its float32 cast.  The gap is
# set by bfloat16's 8 significant bits, mostly in the bf16 logits
# themselves: at full width with 1, 2 and 4 layers (CPU) the largest gap
# over the 151936 entries was 0.029, 0.032 and 0.034 nats, growing at most
# linearly, so about 0.08 at 28 layers; a TPU v5e at 28 layers gave 0.044.
# A broken layer (wrong rope, mask, cache slot or kernel) moves whole nats.
FP32_TOL = 0.2
# Mesh against single device: the same bf16 program with its reductions
# split over the ``model`` axis and all-reduced in another order, a smaller
# perturbation than bf16 against float32; a wrong shard moves whole nats.
MESH_TOL = 0.2


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------- kernels


def _decode_case(key, B, Hq, Hkv, S, D, T):
    """Decode-shaped inputs: row b holds a left-padded context in
    [starts[b], lengths[b]); its T queries sit at the last T positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, T, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.bfloat16)
    rng = np.random.RandomState(0)
    lengths = rng.randint(T + 1, S + 1, size=B).astype(np.int32)
    lengths[0] = S
    starts = np.array([rng.randint(0, (n - T) // 2 + 1) for n in lengths],
                      np.int32)
    k_pos = np.full((B, S), -1, np.int32)
    q_pos = np.zeros((B, T), np.int32)
    for b in range(B):
        k_pos[b, starts[b]:lengths[b]] = np.arange(lengths[b] - starts[b])
        q_pos[b] = k_pos[b, lengths[b] - T:lengths[b]]
    return (q, k, v, jnp.asarray(q_pos), jnp.asarray(k_pos),
            jnp.asarray(lengths), jnp.asarray(starts))


def phase_kernels(cfg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.cache_gather.ops import cache_roll, paged_gather
    from repro.kernels.cache_slot_write.ops import cache_slot_write
    from repro.kernels.decode_attention.ops import (decode_attention,
                                                    paged_decode_attention)
    from repro.kernels.spec_verify.ops import spec_verify

    B, Hq, Hkv, D = 8, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    S, bs = 2048, cfg.kv_block_size
    key = jax.random.PRNGKey(7)
    for T in (1, 5):
        q, k, v, qp, kp, ln, st = _decode_case(key, B, Hq, Hkv, S, D, T)
        got = decode_attention(q, k, v, qp, kp, ln, st, impl="pallas")
        want = decode_attention(q, k, v, qp, kp, ln, st, impl="blocked")
        err = float(jnp.max(jnp.abs(got - want)))
        print(f"kernels: decode_attention dense T={T} max|pallas-blocked|="
              f"{err:.3e} (tol {ATTN_TOL})", flush=True)
        check(np.isfinite(err) and err <= ATTN_TOL, "dense decode mismatch")

        # the same logical cache, paged through a shuffled block table
        nb = S // bs
        perm = np.random.RandomState(T).permutation(B * nb) + 1
        table = jnp.asarray(perm.reshape(B, nb).astype(np.int32))
        NB = B * nb + 1

        def to_pool(x):
            blocks = x.reshape(B, Hkv, nb, bs, D).transpose(0, 2, 1, 3, 4)
            pool = jnp.zeros((NB, Hkv, bs, D), x.dtype)
            return pool.at[table.reshape(-1)].set(
                blocks.reshape(B * nb, Hkv, bs, D))

        kpool, vpool = to_pool(k), to_pool(v)
        got = paged_decode_attention(q, kpool, vpool, table, qp, kp, ln, st,
                                     impl="pallas")
        err_p = float(jnp.max(jnp.abs(got - want)))
        print(f"kernels: decode_attention paged T={T} "
              f"max|pallas-blocked|={err_p:.3e} (tol {ATTN_TOL})", flush=True)
        check(np.isfinite(err_p) and err_p <= ATTN_TOL,
              "paged decode mismatch")

    # cache surgery is data movement: Pallas must equal the reference
    R = 64
    buf = jax.random.normal(key, (R, S, D), jnp.bfloat16)
    shift = jax.random.randint(key, (R,), 0, S + 1, jnp.int32)
    # a whole number of sublane tiles, and a trainer-like ragged width
    same_roll = all(
        bool(jnp.array_equal(cache_roll(b, shift % (b.shape[1] + 1),
                                        impl="pallas"),
                             cache_roll(b, shift % (b.shape[1] + 1),
                                        impl="ref")))
        for b in (buf, buf[:, :106]))
    pool = jax.random.normal(key, (NB, Hkv * bs, D), jnp.bfloat16)
    same_gather = bool(jnp.array_equal(
        paged_gather(pool, table, impl="pallas"),
        paged_gather(pool, table, impl="ref")))
    rows = jnp.asarray([5, 17, 40, 63], jnp.int32)
    src = jax.random.normal(jax.random.PRNGKey(8), (4, S, D), jnp.bfloat16)
    same_write = bool(jnp.array_equal(
        cache_slot_write(buf, src, rows, impl="pallas"),
        cache_slot_write(buf, src, rows, impl="ref")))
    kv = jax.random.split(key, 3)
    lp_c = -jax.random.exponential(kv[0], (16, 512))
    lp_p = -jax.random.exponential(kv[1], (16, 512))
    u = jax.random.uniform(kv[2], (16, 512))
    vlen = jnp.arange(16, dtype=jnp.int32) * 32
    same_verify = bool(jnp.array_equal(
        spec_verify(lp_c, lp_p, u, vlen, 0.5, impl="pallas"),
        spec_verify(lp_c, lp_p, u, vlen, 0.5, impl="ref")))
    print(f"kernels: exact against reference: cache_roll={same_roll} "
          f"paged_gather={same_gather} cache_slot_write={same_write} "
          f"spec_verify={same_verify}", flush=True)
    check(same_roll and same_gather and same_write and same_verify,
          "a cache-surgery or verify kernel differs from its reference")


# ------------------------------------------------------------------- fp32


def phase_fp32(cfg) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data.tokenizer import VOCAB_SIZE
    from repro.models import model as M

    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    B, T = 4, 48
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 3, VOCAB_SIZE)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    @jax.jit
    def first_step(p, toks):
        logits, _ = M.forward(p, cfg, toks, pos)
        return jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), -1)

    cfg32 = cfg.replace(dtype="float32", param_dtype="float32")

    @jax.jit
    def first_step32(p, toks):
        with jax.default_matmul_precision("highest"):
            logits, _ = M.forward(p, cfg32, toks, pos)
        return jax.nn.log_softmax(logits[:, -1], -1)

    lsm = first_step(params, tokens)
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    del params
    ref = first_step32(params32, tokens)
    diff = jnp.abs(lsm - ref)
    err, mean = float(jnp.max(diff)), float(jnp.mean(diff))
    top1 = float(jnp.mean(jnp.argmax(lsm, -1) == jnp.argmax(ref, -1)))
    print(f"fp32: first-step log-softmax bf16 vs fp32 max|d|={err:.4f} "
          f"mean|d|={mean:.2e} top1_agree={top1:.2f} (tol {FP32_TOL})",
          flush=True)
    check(np.isfinite(err) and err <= FP32_TOL,
          "bf16 model departs from its float32 reference")


# ------------------------------------------------------------------ train


def train_args(extra=()) -> list:
    # lenience 0.9 < 1: with an unmoved random policy every draft token
    # would otherwise be accepted and the resume decode would have nothing
    # to do; at 0.9 each row rejects after ~10 tokens and decodes the rest
    return ["--arch", ARCH, "--algo", "grpo", "--variant", "spec",
            "--lenience", "0.9", "--prompts-per-batch", "2", "--group-size",
            "4", "--problems", "2", "--max-new-tokens", "96", *extra]


def phase_train(argv, steps: int):
    import numpy as np
    from repro.launch import train
    from repro.obs.alerts import jit_cache_size
    from repro.rl.trainer import _update_actor

    args = train.parse_args(argv)
    tr = train.build_trainer(args)
    compiles0 = jit_cache_size(_update_actor)
    hist = []
    for i in range(steps):
        t0 = time.perf_counter()
        m = tr.train_step()
        dt = time.perf_counter() - t0
        hist.append(m)
        print(f"train: step {i} loss={m['loss']:.5f} "
              f"reward={m['reward_mean']:.3f} one_pass={m['one_pass']:.0f} "
              f"n_reused={m['n_reused']:.0f} "
              f"n_generated={m['n_generated']:.0f} wall={dt:.1f}s",
              flush=True)
        check(np.isfinite(m["loss"]) and np.isfinite(m["reward_mean"]),
              f"step {i}: non-finite loss or reward")
        if i >= 1:
            check(m["one_pass"] == 1.0, f"step {i}: one-pass path not taken")
            check(m["n_reused"] > 0, f"step {i}: no prefix reused")
    n_comp = jit_cache_size(_update_actor) - compiles0
    print(f"train: _update_actor compiled {n_comp} time(s) in {steps} steps",
          flush=True)
    check(n_comp == 1, "the update step compiled more than once")
    return tr, hist


# ------------------------------------------------------------------ serve

# counters that must stay zero on a clean serve: quarantines, retries,
# timeouts, sheds, rejections and the decode-impl ladder (fault_* holds
# every recovery action the engine can take)
CLEAN_KEYS = ("quarantined_requests", "retried_requests", "timeouts",
              "shed_requests", "rejected_requests")


def serve_args(extra=()) -> list:
    return ["--no-smoke", "--arch", ARCH, "--slots", "8", "--requests", "16",
            "--prompt-len", "64", "--max-new-tokens", "448", *extra]


def phase_serve(argv, label: str):
    from repro.launch import serve
    from repro.serving.request import FAILURE_REASONS
    t0 = time.perf_counter()
    out = serve.serve(argv)
    dt = time.perf_counter() - t0
    s, resps = out["stats"], out["responses"]
    bad = {k: v for k, v in s.items()
           if (k in CLEAN_KEYS or k.startswith("fault_"))
           and isinstance(v, float) and v != 0.0}
    reasons = sorted({r.finish_reason for r in resps.values()})
    print(f"serve[{label}]: {len(resps)}/{out['requests']} finished "
          f"({', '.join(reasons)}), generated={s['generated_tokens']:.0f} "
          f"reused={s['reused_tokens']:.0f} wall={dt:.1f}s "
          f"nonzero recovery counters: {bad or 'none'}", flush=True)
    check(len(resps) == out["requests"] and not out["interrupted"],
          f"serve[{label}]: not every request finished")
    check(not any(r.finish_reason in FAILURE_REASONS
                  for r in resps.values()),
          f"serve[{label}]: failed requests {reasons}")
    check(not bad, f"serve[{label}]: recovery counters fired: {bad}")
    return out


# ------------------------------------------------------------------- mesh


def _device_set(tree) -> set:
    import jax
    out = set()
    for leaf in jax.tree.leaves(tree):
        out |= set(leaf.sharding.device_set)
    return out


def _teacher_forced_gap(cfg, params, rows, device) -> float:
    """Max |mesh logprob - single-device teacher-forced logprob| over the
    sampled tokens.  rows: (prompt tokens, response tokens, response
    logprobs) per sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.engine.generate import score

    P = max(len(p) for p, _, _ in rows)
    N = max(len(t) for _, t, _ in rows)
    B = len(rows)
    toks = np.zeros((B, P + N), np.int32)
    mask = np.zeros((B, P + N), bool)
    for i, (p, t, _) in enumerate(rows):
        toks[i, P - len(p):P], mask[i, P - len(p):P] = p, True
        toks[i, P:P + len(t)], mask[i, P:P + len(t)] = t, True
    single = jax.device_put(params, device)
    lp = jax.jit(lambda p, t, m: score(p, cfg, t, m)["logprobs"])(
        single, jax.device_put(jnp.asarray(toks), device),
        jax.device_put(jnp.asarray(mask), device))
    lp = np.asarray(lp)
    gap = 0.0
    for i, (_, t, mesh_lp) in enumerate(rows):
        n = len(t)
        if n:
            gap = max(gap, float(np.max(np.abs(lp[i, P:P + n]
                                               - np.asarray(mesh_lp)[:n]))))
    return gap


def phase_mesh() -> None:
    import jax
    import numpy as np

    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh_flags = ["--mesh-data", "2", "--mesh-model", "2"]

    # train: two steps on the 2x2 mesh; the second step's rollout is
    # re-scored on one device under the params that sampled it
    args = train_args(["--max-new-tokens", "64", *mesh_flags])
    from repro.launch import train
    tr = train.build_trainer(train.parse_args(args))
    span = _device_set(tr.params) | _device_set(tr.opt_state)
    print(f"mesh-train: params/opt state span {len(span)} devices",
          flush=True)
    check(len(span) == 4, "trainer state does not span the 4 devices")
    m = tr.train_step()
    rollout_params = jax.device_get(tr.params)
    m = tr.train_step()
    print(f"mesh-train: step 1 loss={m['loss']:.5f} "
          f"one_pass={m['one_pass']:.0f} n_reused={m['n_reused']:.0f}",
          flush=True)
    check(np.isfinite(m["loss"]), "mesh train: non-finite loss")
    rb = tr.last_rb
    rows = []
    for i in range(rb.prompt.shape[0]):
        n = int(rb.length[i])
        rows.append((rb.prompt[i][rb.prompt_mask[i]], rb.response[i, :n],
                     rb.behaviour_logprobs[i, :n]))
    gap = _teacher_forced_gap(tr.cfg, rollout_params, rows, devs[0])
    print(f"mesh-train: max|mesh - single-device teacher-forced logprob|="
          f"{gap:.4f} over {sum(len(r[1]) for r in rows)} tokens "
          f"(tol {MESH_TOL})", flush=True)
    check(gap <= MESH_TOL, "mesh rollout logprobs disagree with one device")
    del tr, rollout_params

    # serve: one slot scheduler per data shard, heads over model
    out = phase_serve(serve_args(["--max-new-tokens", "128", *mesh_flags]),
                      "mesh 2x2")
    eng = out["engine"]
    check(type(eng).__name__ == "MeshSlotServer",
          "serve did not build a MeshSlotServer")
    span = set()
    for e in eng.engines:
        span |= _device_set(e.params) | _device_set(e.caches)
    print(f"mesh-serve: {len(eng.engines)} shards, params and caches span "
          f"{len(span)} devices", flush=True)
    check(len(span) == 4, "serving state does not span the 4 devices")
    rows = [(np.asarray(r.prompt), out["responses"][r.request_id].tokens,
             out["responses"][r.request_id].logprobs)
            for r in out["request_list"]]
    gap = _teacher_forced_gap(out["cfg"], out["params"], rows, devs[0])
    print(f"mesh-serve: max|mesh - single-device teacher-forced logprob|="
          f"{gap:.4f} over {sum(len(r[1]) for r in rows)} tokens "
          f"(tol {MESH_TOL})", flush=True)
    check(gap <= MESH_TOL, "mesh serve logprobs disagree with one device")


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no src/repro package beside {__file__}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"compile cache {cache_dir}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh()
    else:
        cfg = get_config(ARCH)
        phase_kernels(cfg)
        phase_fp32(cfg)
        phase_train(train_args(["--steps", "3"]), 3)
        phase_serve(serve_args(["--spec-prefix", "--draft", "4"]),
                    "spec-prefix draft=4")
        phase_serve(serve_args(["--cache-layout", "paged"]), "paged")
    print(f"total wall {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
