"""Mesh weak-scaling benchmark: data-parallel SPEC-RL rollout throughput.

Runs the one-pass speculative rollout (warm draft cache, so verify →
compact → resume all execute) at a fixed per-shard batch over growing
``data`` axis sizes and records tokens/second and scaling efficiency vs the
single-device run into ``BENCH_mesh.json``.  The d = 2 point is additionally
asserted token-identical to the single-device rollout over the same global
batch — the §8 identity contract, re-proven where the numbers are recorded.

Virtual CPU devices (``--xla_force_host_platform_device_count``) share one
physical CPU, so CPU "scaling" mostly measures partitioning overhead; the
shape of the curve (and the recorded collective layout) is what transfers
to real multi-chip meshes.  The env var is set before jax imports — run as
a module, not via an already-jax-initialised interpreter:

    PYTHONPATH=src python -m benchmarks.mesh_bench --smoke --out BENCH_mesh.json
"""
from __future__ import annotations

import argparse
import json
import os
import time

OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_mesh.json")


def _ensure_virtual_devices(n: int) -> None:
    """Append the host device-count flag BEFORE jax initialises (a later
    os.environ mutation silently no-ops once the backend exists).  The flag
    shapes only the CPU backend; the platform stays JAX's own choice."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def run(smoke: bool = False, out_path: str = OUT_PATH,
        max_data: int = 8) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import RolloutCache, SpecConfig, rollout
    from repro.data.tokenizer import VOCAB_SIZE
    from repro.distributed.mesh import MeshConfig, shard_params
    from repro.engine.generate import GenerateConfig
    from repro.models import model as M
    from repro.models.config import ModelConfig

    from .common import emit

    B_shard = 4 if smoke else 8
    P = 16
    N = 24 if smoke else 48
    iters = 2 if smoke else 5
    cfg = ModelConfig(name="mesh-bench", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=VOCAB_SIZE, max_seq_len=max(256, P + 2 * N))
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    gen = GenerateConfig(max_new_tokens=N, eos_id=VOCAB_SIZE - 1)
    spec = SpecConfig(variant="spec")

    ndev = jax.device_count()
    data_points = [d for d in (1, 2, 4, 8) if d <= min(max_data, ndev)]

    def batch(B, seed=1):
        prompts = jax.random.randint(jax.random.PRNGKey(seed), (B, P), 3,
                                     VOCAB_SIZE - 1)
        mask = jnp.ones((B, P), bool)
        keys = jax.vmap(lambda i: jax.random.fold_in(
            jax.random.PRNGKey(seed + 1), i))(jnp.arange(B))
        return prompts, mask, keys

    def warm_cache(p, B, mesh):
        """Vanilla step 0 fills the draft cache (untimed compile warmup for
        both engine paths rides along)."""
        prompts, mask, keys = batch(B)
        cache = RolloutCache()
        rollout(p, cfg, gen, spec, prompts, mask, list(range(B)), cache,
                jax.vmap(lambda k: jax.random.fold_in(k, 0))(keys), 0,
                mesh=mesh)
        return prompts, mask, keys, cache

    def spec_step(p, B, mesh, prompts, mask, keys, cache, step: int):
        """One warm one-pass speculative step against the evolving cache."""
        return rollout(p, cfg, gen, spec, prompts, mask, list(range(B)),
                       cache,
                       jax.vmap(lambda k: jax.random.fold_in(k, step))(keys),
                       step, mesh=mesh)

    points = []
    base_tok_s = None
    for d in data_points:
        B = B_shard * d
        mesh = MeshConfig(data=d, model=1).build() if d > 1 else None
        p = shard_params(mesh, cfg, params) if mesh is not None else params
        args = warm_cache(p, B, mesh)
        spec_step(p, B, mesh, *args, 1)             # spec-path compile warmup
        # timed region covers ONLY speculative steps — the served tokens
        # (generated + reused) below are produced inside this window
        t0 = time.perf_counter()
        tokens = 0
        for it in range(iters):
            rb = spec_step(p, B, mesh, *args, 2 + it)
            tokens += int(rb.metrics["n_generated"] + rb.metrics["n_reused"])
        dt = time.perf_counter() - t0
        tok_s = tokens / max(dt, 1e-9)
        if base_tok_s is None:
            base_tok_s = tok_s
        pt = {"data": d, "model": 1, "B": B, "time_s": dt, "tokens": tokens,
              "tok_per_s": tok_s, "throughput_vs_1dev": tok_s / base_tok_s,
              "efficiency": tok_s / base_tok_s / d}
        points.append(pt)
        emit(f"mesh/rollout_d{d}", dt * 1e6,
             f"B={B};tok_s={tok_s:.0f};scale={pt['throughput_vs_1dev']:.2f}x")

    # §8 identity: sharded rollout == single-device rollout, same global batch
    identity = False
    if len(data_points) > 1:
        d = data_points[1]
        B = B_shard * d
        mesh = MeshConfig(data=d, model=1).build()
        sp = shard_params(mesh, cfg, params)
        rb_ref = spec_step(params, B, None, *warm_cache(params, B, None), 99)
        rb_mesh = spec_step(sp, B, mesh, *warm_cache(sp, B, mesh), 99)
        np.testing.assert_array_equal(rb_ref.response, rb_mesh.response)
        np.testing.assert_array_equal(rb_ref.length, rb_mesh.length)
        identity = True
        emit("mesh/identity", 0.0, f"d={d};token-identical=True")

    record = {
        "backend": jax.default_backend(),
        "devices": ndev,
        "B_per_shard": B_shard, "P": P, "N": N, "iters": iters,
        "variant": "spec(one-pass)",
        "points": points,
        "identity_checked": identity,
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    emit("mesh/json", 0.0, out_path)
    return record


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="smaller batch/budget (CI lane)")
    ap.add_argument("--out", default=OUT_PATH)
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual device count to request if jax is not "
                         "yet initialised and XLA_FLAGS does not set one")
    ap.add_argument("--max-data", type=int, default=8)
    args = ap.parse_args(argv)
    _ensure_virtual_devices(args.devices)
    run(smoke=args.smoke, out_path=args.out, max_data=args.max_data)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
