"""One run of one cell: set-up, the measured window, the traced batches, the
reference check and the metrics.  ``run.py`` checks for the chip first and
then calls ``run``; tests call ``run`` directly on the CPU.

The window drives ``Collector.collect`` (rl/trainer.py), the collection step
of SPEC-RL's RL loop: rollout (verify -> compact -> resume on reused
prompts, ``generate`` on fresh ones), assembly and reward.  The loop is
closed: the next batch starts when the previous one returns.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import check, flops, peaks, trace
from .traffic import EOS_ID, Batch, Traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WARM_INDEX = 1 << 30           # the warm-up batch: same shapes, own prompts
TRACED_BATCHES = 1
TRACE_ATTEMPTS = 3             # traces tried until one holds whole loops
BATCH_SPAN = "bench.batch"     # host annotation around each traced batch
EPOCH = 1                      # trajectories written in are from epoch 0
REF_ROWS = 2                   # rows per reference forward


# ------------------------------------------------------------ definitions


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, root: str, workload: str):
    """(cell, config entry, traffic spec, per-layer metric defs)."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    entry = load_json(os.path.join(root, conf["file"]))
    spec = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    return cell, entry, spec, per_layer


def family_modules(entry: dict):
    fam = entry["family"]
    ref = load_module(os.path.join(HERE, "configs", fam + ".py"),
                      "onchip_ref_" + fam)
    prog = load_module(os.path.join(HERE, "configs", fam + "_program.py"),
                       "onchip_prog_" + fam)
    return ref, prog


def limits_for(entry: dict) -> Dict[str, float]:
    """The configuration file's ``lp_gap`` limit; the exact counts have 0."""
    return {"lp_gap": float(entry["limits"]["lp_gap"]), "rows_off": 0.0,
            "reused_off": 0.0}


def held_bytes(stats: dict) -> int:
    """Device memory a run held at its peak: the allocator's peak in buffers
    plus the runtime's peak reservation for programs' temporaries, which
    ``peak_bytes_in_use`` leaves out on a TPU."""
    return int(stats.get("peak_bytes_in_use", 0)) + \
        int(stats.get("peak_bytes_reserved", 0))


def enable_cache() -> str:
    """JAX's persistent compilation cache where ``JAX_COMPILATION_CACHE_DIR``
    says (``run.py`` sets it inside the checkout), else in the checkout,
    holding every program: so that a cell's second run in a checkout
    compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def seed_key(seed: int):
    import jax
    word = int(np.random.SeedSequence(int(seed) % (1 << 126))
               .generate_state(1)[0]) & 0x7FFFFFFF
    return jax.random.PRNGKey(word)


# ------------------------------------------------------------ the window


@dataclass
class Record:
    """One collected batch, reduced to what the metrics and check read."""
    index: int
    t0: float
    t1: float
    times: Dict[str, float]
    prompt_tokens: np.ndarray
    prompt_mask: np.ndarray
    response: np.ndarray
    logprobs: np.ndarray
    length: np.ndarray
    n: np.ndarray                    # accepted prefix per row (0 if fresh)
    full_reuse: np.ndarray
    draft: Optional[np.ndarray]
    draft_len: Optional[np.ndarray]
    n_reused: int
    n_generated: int

    @property
    def wall(self) -> float:
        return self.t1 - self.t0

    @property
    def prompt_len(self) -> np.ndarray:
        return self.prompt_mask.sum(axis=1)

    @property
    def generated(self) -> np.ndarray:
        return np.where(self.full_reuse, 0, self.length - self.n)

    @property
    def steps(self) -> int:
        """Decode steps the batch's work needs: its largest per-row
        generated count (what the trace reads is the steps the loop ran)."""
        return int(self.generated.max()) if len(self.length) else 0

    @property
    def decode_rows(self):
        """(context in cache before decoding, generated) per row."""
        ctx = self.prompt_len + self.n
        return [(int(c), int(g)) for c, g in zip(ctx, self.generated)]


class Setup:
    """The system under test for one seed: the benchmark's weights handed
    to the program, the traffic, and a ``Collector`` built as the trainer
    builds it (GRPO, the traffic's group size and token budget)."""

    def __init__(self, entry: dict, spec: dict, seed: int):
        import jax
        from repro.core import SpecConfig
        from repro.rl.trainer import Collector, RLConfig
        self.ref, prog = family_modules(entry)
        self.c = entry["config"]
        self.sizes = self.ref.sizes_of(self.c,
                                       entry.get("eos_logit_margin", 0.0))
        cfg = prog.model_config(entry)
        self.wkey, ckey = jax.random.split(seed_key(seed))
        self.weights = self.ref.init_weights(self.wkey, self.sizes)
        self.params = prog.to_program_params(self.weights)
        prog.check_tree(cfg, self.params)
        self.traffic = Traffic(spec, seed, self.c["vocab_size"])
        rl = RLConfig(algo="grpo", group_size=self.traffic.G,
                      prompts_per_batch=self.traffic.groups,
                      max_new_tokens=self.traffic.N,
                      temperature=float(spec["temperature"]),
                      top_p=float(spec["top_p"]))
        self.collector = Collector(cfg, rl, SpecConfig(**spec.get("spec", {})),
                                   None, ckey)


def put_previous(collector, b: Batch) -> None:
    if b.draft_tokens is not None:
        collector.cache.batch_put(b.cache_keys, b.draft_tokens,
                                  b.draft_logprobs, b.draft_len, EPOCH - 1,
                                  eos_id=EOS_ID)


def collect(collector, params, b: Batch, clock) -> Record:
    from repro.data.dataset import PromptBatch
    t0 = clock()
    put_previous(collector, b)
    pb = PromptBatch(tokens=b.tokens, mask=b.mask, cache_keys=b.cache_keys,
                     answers=[0] * len(b.cache_keys),
                     problem_ids=list(b.cache_keys), epoch=EPOCH)
    _, rb, _, times = collector.collect(params, pb, EPOCH)
    t1 = clock()
    B = b.tokens.shape[0]
    n = b.planned_n if b.planned_n is not None else np.zeros(B, np.int32)
    full = b.full_reuse if b.full_reuse is not None else np.zeros(B, bool)
    return Record(b.index, t0, t1, dict(times), rb.prompt, rb.prompt_mask,
                  np.asarray(rb.response), np.asarray(rb.behaviour_logprobs),
                  np.asarray(rb.length), np.asarray(n), np.asarray(full),
                  b.draft_tokens, b.draft_len,
                  int(times.get("n_reused", 0)),
                  int(times.get("n_generated", 0)))


class CompileCounter:
    """Counts compilations (and persistent-cache loads) while armed."""

    def __init__(self):
        import jax.monitoring
        self.armed = False
        self.events: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and ("backend_compile" in event
                           or "cache_retrieval" in event):
            self.events.append(event)


# ------------------------------------------------------------ context


_TRACE_MODULE = trace


@dataclass
class Context:
    """What a per-layer metric reader may read."""
    config: dict                     # published sizes
    records: List[Record]            # the measured window
    window_s: float
    traced: List[Record] = field(default_factory=list)
    trace: Any = None                # harness.trace.Trace of ``traced``
    trace_lo: float = 0.0
    trace_hi: float = 0.0
    peaks: dict = field(default_factory=dict)
    memory_stats: dict = field(default_factory=dict)
    flops: Any = flops
    trace_mod: Any = _TRACE_MODULE


def read_metrics(defs: List[dict], ctx: Context) -> Dict[str, dict]:
    out = {}
    for m in defs:
        mod = load_module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                          "onchip_metric_" + m["name"].replace(".", "_"))
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ------------------------------------------------------------ the run


def run(cell: dict, entry: dict, spec: dict, per_layer: List[dict], *,
        seed: int, seconds: float, trace_on: bool, t_start: float,
        clock: Callable[[], float] = time.perf_counter,
        log=None) -> dict:
    import jax
    if log is None:
        def log(msg):
            print(f"[{clock() - t_start:7.2f}s] {msg}", file=sys.stderr,
                  flush=True)
    counter = CompileCounter()
    dev = jax.devices()[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    limits = limits_for(entry)
    s = Setup(entry, spec, seed)
    ref, sizes, wkey, traffic, c = s.ref, s.sizes, s.wkey, s.traffic, s.c
    collector, params, weights = s.collector, s.params, s.weights
    del s

    warm = collect(collector, params, traffic.batch(WARM_INDEX), clock)
    log(f"warm batch {warm.wall:.3f}s: one_pass={warm.times.get('one_pass')} "
        f"n_reused={warm.n_reused} n_generated={warm.n_generated}")

    # ---- the measured window: whole cycles of the traffic's prompt
    # lengths, until ``seconds`` have passed, so every window sends each
    # length equally often whatever the speed
    counter.armed = True
    records: List[Record] = []
    setup_s = None
    i = 0
    while True:
        if setup_s is None:
            setup_s = clock() - t_start
        records.append(collect(collector, params, traffic.batch(i), clock))
        i += 1
        if records[-1].t1 - records[0].t0 >= seconds and \
                i % traffic.cycle == 0:
            break
    counter.armed = False
    window_s = records[-1].t1 - records[0].t0
    tokens = int(sum(int(r.length.sum()) for r in records))
    log(f"window {window_s:.3f}s: {len(records)} batches, {tokens} tokens; "
        f"compiles or cache loads inside it: {len(counter.events)}")

    # ---- traced batches, after the window
    collected: List[Record] = []
    traced: List[Record] = []
    tr = None
    lo = hi = 0.0
    if trace_on:
        collected, traced, tr, lo, hi = traced_batches(
            collector, params, traffic, i, c["num_hidden_layers"], clock, log)

    stats = dev.memory_stats() or {}
    mem_peak = held_bytes(stats)
    log(f"device memory: {stats}")

    # ---- free the program's state, then the reference
    del params, weights, collector
    gc.collect()
    all_records = records + collected
    values, _, _ = reference_check(ref, sizes, wkey, all_records, spec,
                                   traffic, seed, c, log)
    ok = check.verdict(values, limits)

    result = {"correct": bool(ok),
              "attempted": int(sum(len(r.length) for r in all_records)),
              "failed": int(values["rows_off"])}
    if trace_on:
        ctx = Context(config=c, records=records,
                      window_s=window_s, traced=traced, trace=tr,
                      trace_lo=lo, trace_hi=hi, peaks=peaks.peaks_for(dev.device_kind),
                      memory_stats=stats)
        result["metrics"] = read_metrics(per_layer, ctx)
    else:
        result["metrics"] = {
            "rollout_tokens_per_s": {"value": tokens / window_s,
                                     "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices()),
                        "memory_peak_bytes": mem_peak}
    if trace_on and tr is not None:
        busy = np.mean([trace.busy_s(tr.ops.get(d, []), lo, hi)
                        for d in sorted(tr.ops)]) if tr.ops else 0.0
        result["device"].update(busy_s=float(busy), window_s=hi - lo)
        result["breakdown"] = breakdown(tr, lo, hi)
    for line in check.lines(values, limits):
        log(line)
    result["check"] = {k: {"value": values[k], "limit": limits[k]}
                       for k in limits}
    return result


def traced_batches(collector, params, traffic, start, layers, clock, log):
    """Trace ``TRACED_BATCHES`` batches after the window.  Where the trace
    lost part of a decode loop (the profiler can drop device events), the
    next batches are traced instead, up to ``TRACE_ATTEMPTS`` times.
    Returns every batch collected here (all are checked for ``correct``),
    the batches of the last trace, the trace and its window."""
    collected: List[Record] = []
    for attempt in range(1, TRACE_ATTEMPTS + 1):
        traced, tr, lo, hi = trace_once(collector, params, traffic,
                                        start + len(collected), clock, log)
        collected += traced
        if trace.whole_loops(tr, [r.steps for r in traced], layers):
            break
        log(f"trace {attempt} of {TRACE_ATTEMPTS} lacks part of a decode "
            f"loop: decode steps read {trace.decode_steps(tr, layers)}, the "
            f"work needs {[r.steps for r in traced]}")
    return collected, traced, tr, lo, hi


def trace_once(collector, params, traffic, start, clock, log):
    import jax
    logdir = tempfile.mkdtemp(prefix="onchip_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    traced = []
    try:
        jax.profiler.start_trace(logdir, profiler_options=opts)
        for j in range(TRACED_BATCHES):
            with jax.profiler.TraceAnnotation(BATCH_SPAN):
                traced.append(collect(collector, params,
                                      traffic.batch(start + j), clock))
        jax.profiler.stop_trace()
        t0 = clock()
        path = trace.find_xplane(logdir)
        tr = trace.load(path)
        lo, hi = trace_window(tr)
        log(f"trace: {os.path.getsize(path)} bytes, read in "
            f"{clock() - t0:.2f}s; devices {sorted(tr.ops)}, "
            f"{sum(len(v) for v in tr.ops.values())} op events")
        return traced, tr, lo, hi
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def trace_window(tr):
    """The traced window on the trace's clock: from the first traced
    batch's start to the last one's end, by their host annotations."""
    spans = [e for e in tr.host if e.name == BATCH_SPAN]
    if len(spans) != TRACED_BATCHES:
        raise ValueError(f"trace holds {len(spans)} {BATCH_SPAN} spans, "
                         f"expected {TRACED_BATCHES}")
    return min(e.start for e in spans), max(e.end for e in spans)


def breakdown(tr, lo: float, hi: float) -> dict:
    """Device seconds by op (leaf ops, by instruction name) and idle
    seconds by what the host was doing, on the first device."""
    dev0 = sorted(tr.ops)[0] if tr.ops else None
    ops = tr.ops.get(dev0, [])
    by_op: Dict[str, float] = {}
    for name, sec in trace.time_by_name(ops, lo, hi).items():
        k = trace.short_name(name)
        by_op[k] = by_op.get(k, 0.0) + sec
    idle = trace.gaps(ops, lo, hi)
    return {"device_ops": trace.top(by_op),
            "idle_gaps": trace.top(trace.label_gaps(idle, tr.host))}


def reference_check(ref, sizes, wkey, records: List[Record], spec, traffic,
                    seed, c, log):
    """The compared numbers, with the sampled rows and their reference
    log-probs (for a control to be read at the same tokens)."""
    V, N = c["vocab_size"], traffic.N
    rows_off = 0
    reused_off = 0
    for r in records:
        for b in range(len(r.length)):
            ok = check.row_ok(r.response[b], r.length[b], N, V,
                              None if r.draft is None else r.draft[b],
                              int(r.n[b]), bool(r.full_reuse[b]),
                              0 if r.draft_len is None else int(r.draft_len[b]))
            rows_off += 0 if ok else 1
        reused_off += abs(r.n_reused - int(r.n.sum()))
    log(f"rows off what the traffic planned: {rows_off}; reused-token count "
        f"off the plan by {reused_off}")

    flat = [(r, b) for r in records for b in range(len(r.length))]
    pick = check.sample_rows([int(r.length[b]) for r, b in flat],
                             int(spec.get("check_rows", 16)), seed)
    rows = [flat[k] for k in pick]
    prog_lp, ref_lp = reference_logprobs(ref, sizes, wkey, rows, traffic,
                                         "reference")
    gap = check.lp_gap(prog_lp, ref_lp)
    log(f"reference: {len(rows)} rows, {sum(len(p) for p in prog_lp)} "
        f"served tokens compared")
    values = {"lp_gap": gap, "rows_off": float(rows_off),
              "reused_off": float(reused_off)}
    return values, rows, ref_lp


def reference_logprobs(ref, sizes, wkey, rows, traffic, mode: str):
    """Program and reference log-probs of the served tokens of ``rows``.
    Weights are drawn again from the seed's key."""
    import jax.numpy as jnp
    T = traffic.P + traffic.N
    w = ref.init_weights(wkey, sizes)
    prog_lp, ref_lp = [], []
    for k in range(0, len(rows), REF_ROWS):
        blk = rows[k:k + REF_ROWS]
        toks = np.zeros((REF_ROWS, T), np.int32)
        lens = np.zeros((REF_ROWS,), np.int32)
        starts = []
        for j, (r, b) in enumerate(blk):
            p = r.prompt_tokens[b][r.prompt_mask[b]]
            L = int(r.length[b])
            toks[j, :len(p)] = p
            toks[j, len(p):len(p) + L] = r.response[b, :L]
            lens[j] = len(p) + L
            starts.append(len(p))
        lp = np.asarray(ref.token_logprobs(w, sizes, jnp.asarray(toks),
                                           jnp.asarray(lens), mode))
        for j, (r, b) in enumerate(blk):
            L = int(r.length[b])
            prog_lp.append(r.logprobs[b, :L])
            ref_lp.append(lp[j, starts[j]:starts[j] + L])
    del w
    return prog_lp, ref_lp
