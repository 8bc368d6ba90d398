"""Serving launcher: continuous-batching slot server with a request arrival
stream, speculative-prefix admission, and latency/throughput stats
(DESIGN.md §6).  Falls back to one-shot fixed-batch generation for trunks
the slot engine does not cover (recurrent state, encoder/vision extras).

    PYTHONPATH=src python -m repro.launch.serve --smoke
    PYTHONPATH=src python -m repro.launch.serve --no-smoke --arch qwen3-0.6b \
        --requests 64 --slots 8 --spec-prefix   # full published width
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve --smoke \
        --mesh-data 2 --mesh-model 2      # one scheduler per data shard (§8)
"""
from __future__ import annotations

import argparse
import random
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core.cache import RolloutCache
from repro.data.dataset import PromptDataset
from repro.data.tokenizer import VOCAB_SIZE, decode
from repro.distributed.mesh import MeshConfig, data_size, shard_params
from repro.launch.compile_cache import enable_compile_cache
from repro.engine.generate import GenerateConfig, generate
from repro.models import model as M
from repro.rewards.mathgen import MathTaskConfig, generate_problems
from repro.serving import Request, make_slot_engine

# long-tailed per-request budgets (fractions of --max-new-tokens): most
# requests are short, a few run to the full budget — the regime where
# fixed-batch decode idles on its stragglers
TAIL_FRACTIONS = (0.25, 0.25, 0.5, 1.0)
TAIL_WEIGHTS = (0.5, 0.25, 0.15, 0.1)


def build_requests(ds: PromptDataset, rng: random.Random, n_requests: int,
                   max_new_tokens: int, key) -> list:
    batch = ds.sample_batch(rng, n_requests, 1)
    keys = np.asarray(jax.vmap(
        lambda i: jax.random.fold_in(key, i))(jnp.arange(n_requests)))
    reqs = []
    for i in range(n_requests):
        p_len = int(batch.mask[i].sum())
        budget = max(1, int(max_new_tokens *
                            rng.choices(TAIL_FRACTIONS, TAIL_WEIGHTS)[0]))
        reqs.append(Request(
            request_id=i, prompt=batch.tokens[i, -p_len:].astype(np.int32),
            key=keys[i], max_new_tokens=budget))
    return reqs


def _model_extras(params, cfg, batch: int, seed: int = 1):
    """Stub modality conditioning for encoder / vision trunks (the same
    placeholder inputs the engine tests use)."""
    kw = {}
    if cfg.encoder_layers:
        frames = jax.random.normal(jax.random.PRNGKey(seed),
                                   (batch, cfg.encoder_frames, cfg.d_model))
        enc, pos = M.encode(params, cfg, frames)
        kw = {"encoder_out": enc, "encoder_positions": pos}
    if cfg.num_prefix_embeddings:
        kw["prefix_embeds"] = jax.random.normal(
            jax.random.PRNGKey(seed + 1),
            (batch, cfg.num_prefix_embeddings, cfg.d_model))
    return kw


def serve_fixed(params, cfg, gen, reqs, prompt_width, slots):
    """Fixed-batch baseline: decode ``slots``-sized batches to the slowest
    row (legacy serve.py behaviour).  Returns (tokens dict, n_generated)."""
    outs, total = {}, 0
    for lo in range(0, len(reqs), slots):
        chunk = reqs[lo:lo + slots]
        B = len(chunk)
        toks = np.zeros((B, prompt_width), np.int32)
        mask = np.zeros((B, prompt_width), bool)
        for j, r in enumerate(chunk):
            toks[j, prompt_width - len(r.prompt):] = r.prompt
            mask[j, prompt_width - len(r.prompt):] = True
        keys = jnp.asarray(np.stack([r.key for r in chunk]))
        budget = jnp.asarray([r.max_new_tokens for r in chunk], jnp.int32)
        out = generate(params, cfg, gen, jnp.asarray(toks), jnp.asarray(mask),
                       keys, row_budget=budget,
                       **_model_extras(params, cfg, B))
        jax.block_until_ready(out["tokens"])
        for j, r in enumerate(chunk):
            L = int(out["length"][j])
            outs[r.request_id] = np.asarray(out["tokens"][j, :L])
        total += int(out["n_generated"])
    return outs, total


def serve(argv=None) -> dict:
    """Run the serving launcher.  Returns what it served: ``stats`` (the
    engine's ``stats()``, None for the fixed-batch engine), ``responses``
    ({request_id: Response}, or token arrays for the fixed-batch engine),
    ``requests`` (the number submitted), ``request_list``, ``interrupted``,
    and the ``engine``, ``cfg`` and ``params`` that served them."""
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=sorted(ARCH_IDS), default="qwen3-0.6b")
    p.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="tiny reduced config and budget (default); "
                        "--no-smoke serves the published config with the "
                        "full request/token budget")
    p.add_argument("--engine", choices=["auto", "slots", "fixed"],
                   default="auto")
    p.add_argument("--slots", type=int, default=4,
                   help="decode-batch slots (also the fixed-batch size)")
    p.add_argument("--requests", type=int, default=None)
    p.add_argument("--max-new-tokens", type=int, default=None)
    p.add_argument("--prompt-len", type=int, default=10)
    p.add_argument("--arrival-every", type=int, default=0,
                   help="stagger arrivals: one request every K engine steps "
                        "(0 = all queued up front)")
    p.add_argument("--spec-prefix", action="store_true",
                   help="serve every request twice: the first pass's output "
                        "becomes the second pass's speculative prefix")
    p.add_argument("--draft", type=int, default=0, metavar="K",
                   help="continuation draft engine (§9): draft up to K "
                        "tokens per decode forward from n-gram matches over "
                        "each request's own stream (and, with --spec-prefix, "
                        "its first-pass trajectory as corpus); 0 = off")
    p.add_argument("--mesh-data", type=int, default=1,
                   help="data shards — one slot scheduler per shard (§8)")
    p.add_argument("--mesh-model", type=int, default=1,
                   help="model-parallel axis size per shard")
    p.add_argument("--deadline-steps", type=int, default=0,
                   help="§10 per-request decode-step deadline (0 = none): "
                        "expired requests are reclaimed and retried once")
    p.add_argument("--max-queue", type=int, default=0,
                   help="§10 bounded admission queue (0 = unbounded)")
    p.add_argument("--overflow", choices=["reject", "shed-oldest"],
                   default="reject",
                   help="backpressure policy when the queue is full")
    p.add_argument("--ledger", action="store_true",
                   help="§14 token-provenance ledger: account every emitted "
                        "token to its mechanism (reused prefix / accepted "
                        "draft / bonus / fresh / retry / shared block) and "
                        "print the savings-attribution report after the run")
    p.add_argument("--decision-log", default="", metavar="DIR",
                   help="§14 decision-record logging: one (features, "
                        "outcomes) record per draft decision, sharded as "
                        "JSONL + NPZ under DIR (obs.ledger.load_dataset "
                        "reloads them as a training-ready bundle)")
    p.add_argument("--assert-compile-stable", action="store_true",
                   help="§14 recompile sentinel: replay the identical "
                        "request set on a fresh engine after the run and "
                        "fail if any registered jit entry compiles again "
                        "(steady-state compile stability)")
    p.add_argument("--trace-dir", default="",
                   help="§11 observatory: write trace.json (Chrome trace, "
                        "load at ui.perfetto.dev), events.jsonl and "
                        "metrics.prom here after the run")
    p.add_argument("--trace-sample-rate", type=float, default=1.0,
                   help="fraction of requests given their own trace lane "
                        "(deterministic per-request hash)")
    p.add_argument("--metrics", type=int, default=0, metavar="PORT",
                   help="serve Prometheus text exposition on "
                        "http://localhost:PORT/metrics during the run "
                        "(0 = off)")
    p.add_argument("--state-path", default="",
                   help="on SIGTERM/Ctrl-C, snapshot the exact server state "
                        "here (checkpoint/io.save_server_state) for "
                        "kill-and-resume; empty = drain without snapshot")
    p.add_argument("--cache-layout", choices=["dense", "paged"],
                   default="dense",
                   help="§13 KV cache layout: 'paged' serves over a block "
                        "pool with CoW GRPO prompt sharing (token-identical "
                        "to dense; resident batch at fixed HBM grows by the "
                        "per-row block-rounding margin)")
    p.add_argument("--kv-block-size", type=int, default=0,
                   help="paged KV block size in tokens (0 = config default)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    enable_compile_cache()

    n_requests = args.requests or (8 if args.smoke else 64)
    max_new = args.max_new_tokens or (12 if args.smoke else 64)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced(vocab_size=max(VOCAB_SIZE, 64))
    if cfg.vocab_size < VOCAB_SIZE:
        cfg = cfg.replace(vocab_size=VOCAB_SIZE)
    if args.cache_layout != cfg.cache_layout:
        cfg = cfg.replace(cache_layout=args.cache_layout)
    if args.kv_block_size > 0:
        cfg = cfg.replace(kv_block_size=args.kv_block_size)
    params = M.init_lm(jax.random.PRNGKey(args.seed), cfg)
    gen = GenerateConfig(max_new_tokens=max_new)
    mesh = MeshConfig(data=args.mesh_data, model=args.mesh_model).build()
    if mesh is not None and data_size(mesh) <= 1:
        # model-only mesh: shard params here; the slot engine head-shards
        # its caches from the same mesh
        params = shard_params(mesh, cfg, params)

    draft = None
    if args.draft > 0:
        from repro.drafting import DraftConfig
        draft = DraftConfig(kind="ngram", draft_k=args.draft)

    # §11: one explicit tracer for the MAIN serving engine only (the
    # spec-prefix warm pass below builds its cache untraced, keeping the
    # trace about the speculative serve itself)
    tracer = None
    if args.trace_dir:
        from repro.obs import Tracer
        tracer = Tracer(enabled=True, sample_rate=args.trace_sample_rate)

    # §14: the ledger is handed ONLY to the main (traced) engine — the
    # spec-prefix warm pass and the compile-stability replay run without it
    # so the attribution report is about the speculative serve itself
    ledger = None
    if args.ledger:
        from repro.obs.ledger import TokenLedger
        ledger = TokenLedger(enabled=True)

    def make_engine(spec_prefix: bool, traced: bool = False):
        return make_slot_engine(params, cfg, gen, mesh=mesh,
                                num_slots=args.slots,
                                prompt_width=args.prompt_len,
                                spec_prefix=spec_prefix, log_lenience=0.0,
                                draft=draft,
                                deadline_steps=args.deadline_steps or None,
                                max_queue=args.max_queue or None,
                                overflow=args.overflow,
                                tracer=tracer if traced else None,
                                ledger=ledger if traced else None)

    rng = random.Random(args.seed)
    problems = generate_problems(MathTaskConfig(num_problems=n_requests))
    ds = PromptDataset(problems, max_prompt_len=args.prompt_len)
    reqs = build_requests(ds, rng, n_requests, max_new,
                          jax.random.PRNGKey(args.seed + 3))

    engine_kind = args.engine
    if engine_kind == "auto":
        engine_kind = "slots" if M.supports_slot_serving(cfg) else "fixed"
    if engine_kind == "slots" and not M.supports_slot_serving(cfg):
        raise SystemExit(f"--engine slots unsupported for arch {cfg.name} "
                         "(recurrent trunk or modality extras)")
    if engine_kind == "fixed" and (args.spec_prefix or args.arrival_every
                                   or args.draft):
        raise SystemExit(
            f"--spec-prefix/--arrival-every/--draft need the slot engine, "
            f"but engine resolved to 'fixed' for arch {cfg.name}; drop the "
            "flags or pick a slot-capable --arch")

    t0 = time.time()
    if engine_kind == "fixed":
        outs, n_gen = serve_fixed(params, cfg, gen, reqs, args.prompt_len,
                                  args.slots)
        dt = time.time() - t0
        print(f"arch={cfg.name} engine=fixed: served {n_requests} requests, "
              f"{n_gen} tokens in {dt:.2f}s ({n_gen / max(dt, 1e-9):.0f} tok/s)")
        for i in range(min(n_requests, 4)):
            print(f"  req{i}: {decode(outs[i])!r}")
        return {"stats": None, "responses": outs, "requests": n_requests,
                "request_list": reqs, "interrupted": False, "engine": None,
                "cfg": cfg, "params": params}

    drafts = None

    def _attach_spec(reqs_):
        vkeys = np.asarray(jax.vmap(
            lambda i: jax.random.fold_in(jax.random.PRNGKey(args.seed + 11), i)
        )(jnp.arange(n_requests)))
        for i, r in enumerate(reqs_):
            e = drafts.get(r.request_id)
            r.verify_key = vkeys[i]
            r.draft_tokens, r.draft_logprobs = e.tokens, e.logprobs
            r.draft_eos = e.ends_with_eos
            if draft is not None:
                # first-pass trajectory doubles as the §9 n-gram corpus
                r.ngram_corpus = [e.tokens]

    if args.spec_prefix:
        # pass 1 (vanilla) builds the draft cache; pass 2 below serves with
        # speculative-prefix admission against the same policy
        warm = make_engine(spec_prefix=False)
        for r in reqs:
            warm.submit(Request(request_id=r.request_id, prompt=r.prompt,
                                key=r.key, max_new_tokens=r.max_new_tokens))
        warm_resp = warm.run()
        drafts = RolloutCache()
        for i, r in enumerate(reqs):
            resp = warm_resp[r.request_id]
            drafts.put(r.request_id, resp.tokens, resp.logprobs, resp.length,
                       step=0, eos_id=gen.eos_id)
        _attach_spec(reqs)
        t0 = time.time()

    if args.decision_log:
        # the global decision log is configured AFTER the warm pass so the
        # dataset holds only the speculative serve's decisions
        from repro.obs import configure
        from repro.obs.ledger import DecisionLog
        configure(decisions=DecisionLog(args.decision_log, enabled=True))

    engine = make_engine(spec_prefix=args.spec_prefix, traced=True)

    metrics_srv = None
    if args.metrics:
        from repro.obs.export import start_metrics_server
        metrics_srv = start_metrics_server(engine.metrics_registry,
                                           args.metrics)
        print(f"metrics: http://localhost:{args.metrics}/metrics")

    # §10 graceful shutdown: SIGTERM folds into KeyboardInterrupt, and an
    # interrupted serve stops at a chunk boundary (run() only yields control
    # between chunks, where host state is consistent), snapshots the exact
    # server state for kill-and-resume, and still prints final stats
    def _sigterm(signum, frame):
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _sigterm)
    interrupted = False
    try:
        if args.arrival_every > 0:
            arrivals = [(i * args.arrival_every, r)
                        for i, r in enumerate(reqs)]
            resps = engine.run(arrivals=arrivals)
        else:
            for r in reqs:
                engine.submit(r)
            resps = engine.run()
    except KeyboardInterrupt:
        interrupted = True
        resps = engine.responses
        if args.state_path:
            from repro.checkpoint.io import save_server_state
            save_server_state(args.state_path, engine,
                              metadata={"arch": cfg.name,
                                        "requests": n_requests})
            print(f"\ninterrupted: server state -> {args.state_path} "
                  "(resume via checkpoint/io.load_server_state)")
        else:
            print("\ninterrupted: draining without snapshot "
                  "(--state-path to keep serving state)")
    dt = time.time() - t0
    if metrics_srv is not None:
        metrics_srv.shutdown()
    if args.decision_log:
        from repro.obs import get_decision_log
        dec = get_decision_log()
        dec.flush()
        print(f"decisions: {dec.records_total} records -> "
              f"{args.decision_log} (obs.ledger.load_dataset to reload)")
    report = None
    if args.ledger:
        # §14: provenance counts x measured decode cost -> seconds saved
        # per mechanism; the actual wall clock anchors the counterfactual
        from repro.obs.attrib import build_report, measured_token_cost
        regd = engine.metrics_registry().as_dict()
        n_all = max(1, int(ledger.category_counts().sum()))
        t_tok = measured_token_cost(regd) or dt / n_all
        report = build_report(ledger, t_tok, actual_s=dt)
        print(report.summary())
    if args.trace_dir:
        import os
        from repro.obs import export as obs_export
        os.makedirs(args.trace_dir, exist_ok=True)
        reg = engine.metrics_registry()
        counters = None
        if report is not None:
            report.to_registry(reg)    # attribution joins /metrics + prom
            counters = report.counter_events(dt)
        obs_export.write_chrome_trace(
            os.path.join(args.trace_dir, "trace.json"), tracer,
            counters=counters)
        obs_export.write_jsonl(
            os.path.join(args.trace_dir, "events.jsonl"), tracer, reg)
        obs_export.write_prometheus(
            os.path.join(args.trace_dir, "metrics.prom"), reg)
        print(f"trace: {args.trace_dir}/trace.json (load at "
              f"ui.perfetto.dev), events.jsonl, metrics.prom")
    s = engine.stats()
    n_gen = int(s["generated_tokens"])
    shards = int(s.get("num_shards", 1))
    served = len(resps)
    print(f"arch={cfg.name} engine=slots(spec={args.spec_prefix}, "
          f"shards={shards}){' [interrupted]' if interrupted else ''}: served "
          f"{served}/{n_requests} requests, {n_gen} generated "
          f"(+{int(s['reused_tokens'])} reused) tokens in {dt:.2f}s "
          f"({(n_gen + int(s['reused_tokens'])) / max(dt, 1e-9):.0f} tok/s)")
    print(f"  occupancy={s['occupancy']:.2f} engine_steps={int(s['engine_steps'])} "
          f"admissions={int(s['admitted'])} "
          f"mean_queue_wait={s['mean_queue_wait'] * 1e3:.1f}ms "
          f"mean_serve={s['mean_serve_time'] * 1e3:.1f}ms")
    recov = {k: int(s[k]) for k in ("timeouts", "retried_requests",
                                    "shed_requests", "fault_quarantines",
                                    "fault_impl_fallbacks") if s.get(k)}
    if recov:
        print(f"  recovery: {recov}")
    if draft is not None:
        print(f"  draft: tok/fwd={s['tokens_per_forward']:.2f} "
              f"accept={s['accept_rate']:.2f} "
              f"mean_len={s['mean_draft_len']:.2f} "
              f"forwards={int(s['decode_forwards'])}")
    for i in range(min(n_requests, 4)):
        r = resps.get(i)
        if r is None:
            print(f"  req{i} [in-flight at interrupt]")
            continue
        full = np.concatenate([
            np.asarray(reqs[i].draft_tokens[:r.n_accepted], np.int32)
            if r.n_accepted else np.zeros(0, np.int32), r.tokens])
        print(f"  req{i} [{r.finish_reason}]: {decode(full)!r}")

    if args.assert_compile_stable and not interrupted:
        # §14 recompile sentinel: an identical request stream on a fresh
        # engine must hit only already-compiled signatures — any jit cache
        # growth here is a compile in steady state (the recompile_steady_
        # state alert's offline twin)
        from repro.obs.alerts import compile_counts
        baseline = dict(compile_counts())
        reqs2 = build_requests(ds, random.Random(args.seed), n_requests,
                               max_new, jax.random.PRNGKey(args.seed + 3))
        if args.spec_prefix:
            _attach_spec(reqs2)
        replay = make_engine(spec_prefix=args.spec_prefix)
        if args.arrival_every > 0:
            replay.run(arrivals=[(i * args.arrival_every, r)
                                 for i, r in enumerate(reqs2)])
        else:
            for r in reqs2:
                replay.submit(r)
            replay.run()
        grew = {k: (baseline.get(k, 0), v)
                for k, v in compile_counts().items()
                if v != baseline.get(k, 0)}
        if grew:
            raise SystemExit("compile instability: jit cache growth on "
                             f"identical replay: {grew}")
        print(f"compile-stability: {sum(baseline.values())} compiles total, "
              "0 new on identical replay")
    return {"stats": s, "responses": resps, "requests": n_requests,
            "request_list": reqs, "interrupted": interrupted,
            "engine": engine, "cfg": cfg, "params": params}


def main(argv=None):
    serve(argv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
