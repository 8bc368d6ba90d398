"""Distributed training launcher.

On real hardware this runs the RLVR trainer with parameters laid out by the
partition rules over the production mesh; ``--mesh-data/--mesh-model`` build
the runtime mesh (DESIGN.md §8) and the whole rollout → verify → train loop
executes SPMD on it.  A (1, 1) mesh is single-device execution,
token-identical by the §8 contract; a mesh larger than the host raises.  On
a CPU container virtual devices come from
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --smoke --steps 4          # reduced variant, CPU, single device
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --steps 4 --prompts-per-batch 2 --max-new-tokens 96   # full width
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.train --smoke --steps 4 \
        --mesh-data 4 --mesh-model 2
"""
from __future__ import annotations

import argparse
import math

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core import SpecConfig
from repro.data.dataset import PromptDataset
from repro.drafting import DraftConfig
from repro.data.tokenizer import VOCAB_SIZE
from repro.distributed.mesh import MeshConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.adamw import AdamWConfig
from repro.rewards.mathgen import MathTaskConfig, generate_problems
from repro.rl.trainer import RLConfig, Trainer


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=sorted(ARCH_IDS), default="qwen3-1.7b")
    p.add_argument("--algo", choices=["grpo", "ppo", "dapo"], default="grpo")
    p.add_argument("--variant", default="spec",
                   choices=["spec", "off", "random", "delayed", "full"])
    p.add_argument("--lenience", type=float, default=math.e ** 0.5)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--smoke", action="store_true",
                   help="reduced config (CPU-sized) of the same family")
    p.add_argument("--group-size", type=int, default=4)
    p.add_argument("--prompts-per-batch", type=int, default=4)
    p.add_argument("--problems", type=int, default=16,
                   help="size of the generated problem set; at most "
                        "--prompts-per-batch makes every step revisit its "
                        "prompts, so speculation engages from step 1")
    p.add_argument("--max-new-tokens", type=int, default=10)
    p.add_argument("--lr", type=float, default=5e-7)
    p.add_argument("--mesh-data", type=int, default=1,
                   help="data-parallel axis size (1 = off)")
    p.add_argument("--mesh-model", type=int, default=1,
                   help="model-parallel axis size (1 = off)")
    p.add_argument("--draft", type=int, default=0, metavar="K",
                   help="continuation draft engine (§9): draft up to K "
                        "tokens per decode forward from n-gram/sibling "
                        "matches (0 = off)")
    p.add_argument("--draft-fixed", action="store_true",
                   help="disable the adaptive per-row draft length "
                        "controller (always draft K)")
    p.add_argument("--async", dest="async_mode", action="store_true",
                   help="§12 disaggregated mode: continuous rollout service "
                        "feeding a bounded trajectory buffer, consumed by "
                        "the trainer under a bounded staleness window")
    p.add_argument("--staleness-window", type=int, default=1, metavar="K",
                   help="async: accept trajectories <= K policy versions "
                        "old with truncated-IS correction; older ones are "
                        "re-verified through the SPEC-RL draft path (K=0 "
                        "is token-identical to the synchronous trainer)")
    p.add_argument("--buffer-capacity", type=int, default=8,
                   help="async: trajectory buffer bound (shed-oldest past "
                        "it, producer throttles at the high watermark)")
    p.add_argument("--publish-every", type=int, default=1,
                   help="async: publish weights every N optimizer steps")
    p.add_argument("--async-schedule", default="pc",
                   help="async: deterministic producer/consumer interleave "
                        "pattern, e.g. 'pc' or 'ppcc'")
    p.add_argument("--watchdog-dir", default="",
                   help="enable the §10 trainer watchdog: snapshot to this "
                        "directory on healthy steps, restore-last-good and "
                        "skip the batch on non-finite loss / stalled rollout")
    p.add_argument("--watchdog-every", type=int, default=10,
                   help="healthy-step snapshot cadence (steps)")
    p.add_argument("--watchdog-max-collect-time", type=float,
                   default=float("inf"),
                   help="rollout stall threshold in seconds")
    p.add_argument("--ledger", action="store_true",
                   help="§14 token-provenance ledger: account every rollout "
                        "token to its mechanism and print the savings-"
                        "attribution report after the run")
    p.add_argument("--decision-log", default="", metavar="DIR",
                   help="§14 decision-record logging: shard draft-decision "
                        "(features, outcomes) records under DIR — the "
                        "learned draft-length controller's dataset")
    p.add_argument("--alerts", action="store_true",
                   help="§14 metric alert rules: evaluate the default "
                        "threshold/trend rules on every step's metrics; "
                        "events trace on the 'alerts' lane and feed the "
                        "watchdog counters when --watchdog-dir rides along")
    p.add_argument("--trace-dir", default="",
                   help="§11 observatory: write trace.json (Chrome trace, "
                        "load at ui.perfetto.dev), events.jsonl and "
                        "metrics.prom here after the run")
    p.add_argument("--trace-sample-rate", type=float, default=1.0,
                   help="fraction of slot-served requests given their own "
                        "trace lane (deterministic per-request hash)")
    p.add_argument("--metrics", type=int, default=0, metavar="PORT",
                   help="serve Prometheus text exposition on "
                        "http://localhost:PORT/metrics during the run "
                        "(0 = off)")
    return p.parse_args(argv)


def build_trainer(args: argparse.Namespace) -> Trainer:
    """The trainer ``main`` runs, built from parsed arguments.  Tracer,
    ledger and decision log are process-global: configure them first."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced(vocab_size=max(VOCAB_SIZE, 64))
    if cfg.vocab_size < VOCAB_SIZE:
        cfg = cfg.replace(vocab_size=VOCAB_SIZE)

    problems = generate_problems(MathTaskConfig(num_problems=args.problems,
                                                max_operand=9))
    ds = PromptDataset(problems, max_prompt_len=10)
    rl = RLConfig(algo=args.algo, group_size=args.group_size,
                  prompts_per_batch=args.prompts_per_batch,
                  max_new_tokens=args.max_new_tokens,
                  optim=AdamWConfig(lr=args.lr))
    draft = DraftConfig(kind="ngram", draft_k=args.draft,
                        adaptive=not args.draft_fixed) if args.draft > 0 \
        else DraftConfig()
    spec = SpecConfig(variant=args.variant, lenience=args.lenience,
                      verify_impl="auto", draft=draft)
    mesh_cfg = MeshConfig(data=args.mesh_data, model=args.mesh_model)
    watchdog = None
    if args.watchdog_dir:
        from repro.rl.watchdog import TrainWatchdog, WatchdogConfig
        watchdog = TrainWatchdog(WatchdogConfig(
            checkpoint_dir=args.watchdog_dir,
            snapshot_every=args.watchdog_every,
            max_collect_time=args.watchdog_max_collect_time))
    alerts = None
    if args.alerts:
        from repro.obs import get_tracer
        from repro.obs.alerts import AlertManager
        alerts = AlertManager(tracer=get_tracer())
    return Trainer(cfg, rl, spec, ds, jax.random.PRNGKey(0), mesh=mesh_cfg,
                   watchdog=watchdog, alerts=alerts)


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()

    # §11: install the process-global tracer/registry BEFORE the trainer is
    # built so the rollout, drafting and trainer stage hooks all land in it
    tracer = None
    if args.trace_dir or args.metrics:
        from repro.obs import MetricsRegistry, Tracer, configure
        tracer = Tracer(enabled=bool(args.trace_dir),
                        sample_rate=args.trace_sample_rate)
        configure(tracer=tracer, registry=MetricsRegistry())
    # §14: the ledger/decision log are process-global like the tracer — the
    # rollout, drafting loop and slot adapter all record through obs.get_*
    ledger = None
    if args.ledger:
        from repro.obs import configure
        from repro.obs.ledger import TokenLedger
        ledger = TokenLedger(enabled=True)
        configure(ledger=ledger)
    if args.decision_log:
        from repro.obs import configure
        from repro.obs.ledger import DecisionLog
        configure(decisions=DecisionLog(args.decision_log, enabled=True))

    tr = build_trainer(args)
    cfg = tr.cfg
    alerts = tr.alerts
    metrics_srv = None
    if args.metrics:
        from repro.obs import get_registry
        from repro.obs.export import start_metrics_server
        metrics_srv = start_metrics_server(get_registry, args.metrics)
        print(f"metrics: http://localhost:{args.metrics}/metrics")
    mesh_desc = (f"{args.mesh_data}x{args.mesh_model}" if tr.mesh is not None
                 else "off")
    print(f"arch={cfg.name} devices={jax.device_count()} mesh={mesh_desc} "
          f"params={sum(x.size for x in jax.tree.leaves(tr.params)) / 1e6:.1f}M")
    def _step_line(m):
        line = (f"step {m['step']:3.0f} reward={m['reward_mean']:.3f} "
                f"gen_tok={m.get('n_generated', 0):6.0f} "
                f"reused={m.get('n_reused', 0):6.0f}")
        if args.draft > 0:
            line += (f" tok/fwd={m.get('tokens_per_forward', 1.0):.2f} "
                     f"draft_acc={m.get('draft_accept_rate', 0.0):.2f} "
                     f"draft_len={m.get('draft_mean_len', 0.0):.2f}")
        return line

    import time as _time
    t_run0 = _time.time()
    if args.async_mode:
        from repro.rl.async_loop import AsyncConfig, AsyncTrainer
        at = AsyncTrainer(tr, AsyncConfig(
            staleness_window=args.staleness_window,
            buffer_capacity=args.buffer_capacity,
            publish_every=args.publish_every,
            schedule=args.async_schedule))
        print(f"async: K={args.staleness_window} "
              f"buffer={args.buffer_capacity} "
              f"schedule={args.async_schedule!r}")
        sched, i, done, idle = args.async_schedule, 0, 0, 0
        while done < args.steps and idle < 10000:
            role = sched[i % len(sched)]
            i += 1
            if role == "p":
                at.producer_tick()
                continue
            m = at.consumer_step()
            if m is None:
                idle += 1
                continue
            idle, done = 0, done + 1
            print(_step_line(m) +
                  f" staleness={m.get('staleness', 0.0):.0f} "
                  f"mode={m.get('async_mode_level', 0.0):.0f}", flush=True)
        for k, v in sorted(at.counters().items()):
            print(f"async {k}={v:.0f}")
    else:
        for _ in range(args.steps):
            print(_step_line(tr.train_step()), flush=True)
    t_run = _time.time() - t_run0
    if metrics_srv is not None:
        metrics_srv.shutdown()
    if args.decision_log:
        from repro.obs import get_decision_log
        dec = get_decision_log()
        dec.flush()
        print(f"decisions: {dec.records_total} records -> "
              f"{args.decision_log} (obs.ledger.load_dataset to reload)")
    if alerts is not None:
        fired = {k: v for k, v in alerts.as_dict().items() if v}
        print(f"alerts: {fired or 'none fired'}")
    report = None
    if args.ledger:
        from repro.obs import get_registry
        from repro.obs.attrib import build_report, measured_token_cost
        regd = get_registry().as_dict()
        n_all = max(1, int(ledger.category_counts().sum()))
        t_tok = measured_token_cost(regd) or t_run / n_all
        report = build_report(ledger, t_tok, actual_s=t_run)
        print(report.summary())
    if args.trace_dir:
        import os
        from repro.obs import export as obs_export, get_registry
        os.makedirs(args.trace_dir, exist_ok=True)
        reg = get_registry()
        counters = None
        if report is not None:
            report.to_registry(reg)
            counters = report.counter_events(t_run)
        obs_export.write_chrome_trace(
            os.path.join(args.trace_dir, "trace.json"), tracer,
            counters=counters)
        obs_export.write_jsonl(
            os.path.join(args.trace_dir, "events.jsonl"), tracer, reg)
        obs_export.write_prometheus(
            os.path.join(args.trace_dir, "metrics.prom"), reg)
        print(f"trace: {args.trace_dir}/trace.json (load at "
              f"ui.perfetto.dev), events.jsonl, metrics.prom")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
