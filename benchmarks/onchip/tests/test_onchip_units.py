"""Trace reduction, the peaks table and the operation/byte counts of the
on-chip benchmark, on synthetic inputs (CPU)."""
import os

import pytest

import onchip_tiny  # noqa: F401  (puts the harness on sys.path)
from harness import flops, peaks, trace
from harness.cell import Context, Record, load_module

import numpy as np


def ev(name, s, e):
    return trace.Event(name, s, e)


# ----------------------------------------------------------------- trace


def test_union_merges_overlaps_and_drops_empty():
    got = trace.union([(5, 6), (0, 2), (1, 3), (3, 4), (7, 7)])
    assert got == [(0, 4), (5, 6)]


def test_busy_and_idle_share_on_synthetic_ops():
    ops = [ev("a", 0.0, 1.0), ev("b", 0.5, 2.0), ev("c", 3.0, 4.0),
           ev("d", 9.0, 12.0)]
    # window [0, 10]: busy [0,2] + [3,4] + [9,10] = 4 s, idle 6 s
    assert trace.busy_s(ops, 0.0, 10.0) == pytest.approx(4.0)
    gaps = trace.gaps(ops, 0.0, 10.0)
    assert gaps == [(2.0, 3.0), (4.0, 9.0)]
    assert sum(e - s for s, e in gaps) == pytest.approx(6.0)


def test_gap_label_is_innermost_host_event():
    host = [ev("bench.batch", 0.0, 10.0), ev("reward", 4.0, 8.0),
            ev("cache_put", 2.1, 2.9)]
    by = trace.label_gaps([(2.0, 3.0), (4.0, 9.0), (9.5, 9.50001)], host)
    assert by["cache_put"] == pytest.approx(1.0)
    assert by["reward"] == pytest.approx(5.0)     # midpoint 6.5 in reward
    assert by["(gaps under 50 us)"] == pytest.approx(1e-5)


def test_time_by_name_filters_and_clips():
    ops = [ev("decode_kernel.1", 0.0, 1.0), ev("decode_kernel.1", 2.0, 3.0),
           ev("fusion.7", 1.0, 2.0), ev("decode_kernel.1", 9.5, 11.0)]
    by = trace.time_by_name(ops, 0.0, 10.0,
                            keep=lambda e: e.name.startswith("decode"))
    assert by == {"decode_kernel.1": pytest.approx(2.5)}
    assert trace.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                           ["c", 2.0]]


def test_load_reads_host_annotations_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.batch"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(trace.find_xplane(str(tmp_path)))
    spans = [e for e in tr.host if e.name == "bench.batch"]
    assert len(spans) == 1 and spans[0].dur > 0
    assert tr.ops == {}            # no TPU plane in a CPU trace


def kernel_events(name, s, e, k):
    """``k`` back-to-back events of one op tiling [s, e]."""
    w = (e - s) / k
    return [ev(name, s + j * w, s + (j + 1) * w) for j in range(k)]


def synthetic_trace():
    """Two decode-program runs as a TPU trace shows them: a program event,
    its loops (enclosing ops) and leaf ops named by HLO instruction text."""
    mods = [ev("jit_verify_and_prefill(77)", 0.0, 1.0),
            ev("jit_resume_from_cache(12)", 1.0, 5.0),
            ev("jit_generate(3)", 6.0, 9.0)]
    loops = [ev("%while.41 = (s32[], pred[8]) while(...)", 1.1, 4.9),
             ev("%while.42 = (s32[], bf16[8,1,1024]) while(...)", 1.2, 1.3),
             ev("%while.7 = (s32[]) while(...)", 6.0, 6.5),      # prefill scan
             ev("%while.9 = (s32[]) while(...)", 6.6, 8.8),      # decode loop
             ev("%while.1 = (s32[]) while(...)", 9.5, 9.9)]      # other program
    # each decode loop runs 4 steps of the 3 layers of C: 12 kernel events
    ops = [ev("%fusion.3 = bf16[8,768,1024] fusion(...)", 0.0, 1.0),
           *kernel_events("%decode_attention.5 = (f32[8,8,10,2,1]) "
                          "custom-call(...)", 1.2, 2.2, 12),
           ev("%copy.125 = bf16[28,8,8,1280,128] copy(...)", 2.2, 4.9),
           *kernel_events("%decode_attention.2 = (f32[8,8,6,2,1]) "
                          "custom-call(...)", 6.6, 7.6, 12),
           ev("%fusion.9 = f32[8,151936] fusion(...)", 7.6, 8.8)]
    return trace.Trace(ops={0: ops}, loops={0: loops}, modules={0: mods},
                       host=[ev("bench.batch", 0.0, 5.5),
                             ev("bench.batch", 5.5, 10.0),
                             ev("np.asarray(jax.Array)", 5.0, 5.6)])


def test_short_names_and_leaf_ops():
    assert trace.short_name("%decode_attention.5 = (f32[8]) custom-call(x)") \
        == "decode_attention.5"
    assert trace.short_name("jit_resume_from_cache(10165834299268394570)") \
        == "jit_resume_from_cache"
    assert not trace.is_leaf(ev("%while.41 = (s32[]) while(x)", 0, 1))
    assert trace.is_leaf(ev("%copy.1 = bf16[2] copy(x)", 0, 1))


def test_decode_loops_are_the_longest_while_of_each_decode_program():
    loops = trace.decode_loops(synthetic_trace())
    assert [trace.short_name(e.name) for e in loops] == ["while.41",
                                                          "while.9"]
    assert sum(e.dur for e in loops) == pytest.approx(3.8 + 2.2)


def test_decode_steps_count_kernel_events_per_layer():
    tr = synthetic_trace()
    assert trace.decode_steps(tr, 3) == [4, 4]
    assert trace.decode_steps(tr, 2) == [6, 6]
    # a count that is no whole number of steps reads nothing
    assert trace.decode_steps(tr, 5) == []
    # nor does a loop without the kernel
    bare = trace.Trace(ops={0: []}, loops=tr.loops, modules=tr.modules,
                       host=[])
    assert trace.decode_steps(bare, 3) == []


def test_a_trace_that_lost_a_decode_loop_is_not_whole():
    tr = synthetic_trace()
    assert trace.whole_loops(tr, [4, 4], 3)
    assert trace.whole_loops(tr, [3, 4], 3)     # a loop may run past the work
    assert not trace.whole_loops(tr, [4, 5], 3)  # fewer steps than the work
    assert not trace.whole_loops(tr, [4], 3)     # a loop per traced batch
    # the profiler dropped the fresh program's decode ``while``: its longest
    # loop is then the prefill scan, which holds no kernel event, and the
    # readers of the loop read nothing rather than a share far above 100%
    lost = trace.Trace(ops=tr.ops, loops={0: [e for e in tr.loops[0]
                                              if "while.9 " not in e.name]},
                       modules=tr.modules, host=tr.host)
    assert not trace.whole_loops(lost, [4, 4], 3)
    r = _record([1, 1], [False, False], [5, 5], [2, 2])
    ctx = Context(C, [], 1.0, traced=[r, r], trace=lost, trace_lo=0.0,
                  trace_hi=10.0, peaks=peaks.peaks_for("TPU v5 lite"))
    for name in ("decode_ms_per_step", "decode_hbm_share",
                 "decode_attention_roofline"):
        assert _metric(name).read(ctx) is None


def test_traced_batches_trace_again_until_the_loops_are_whole(monkeypatch):
    from harness import cell
    whole, lost = synthetic_trace(), synthetic_trace()
    lost.loops = {0: []}
    got = iter([lost, lost, whole])
    calls = []

    def once(collector, params, traffic, start, clock, log):
        calls.append(start)
        return [_record([1], [False], [5], [2])], next(got), 0.0, 10.0

    monkeypatch.setattr(cell, "trace_once", once)
    collected, traced, tr, lo, hi = cell.traced_batches(
        None, None, None, 7, 3, lambda: 0.0, lambda msg: None)
    assert calls == [7, 8, 9]            # each attempt traces a new batch
    assert len(collected) == 3 and len(traced) == 1 and tr is whole
    # no whole trace within the attempts: the last one is returned
    got = iter([lost] * cell.TRACE_ATTEMPTS)
    calls.clear()
    _, _, tr, _, _ = cell.traced_batches(None, None, None, 0, 3,
                                         lambda: 0.0, lambda msg: None)
    assert tr is lost and len(calls) == cell.TRACE_ATTEMPTS


def _metric(name):
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return load_module(os.path.join(here, "metrics", name + ".py"),
                       "t_" + name)


def test_trace_readers_on_a_synthetic_trace():
    tr = synthetic_trace()
    pk = peaks.peaks_for("TPU v5 lite")
    # batch 1: 2 rows decode 4 steps from a context of 3; batch 2: idle row
    r1 = _record([1, 1], [False, False], [5, 5], [2, 2])
    r2 = _record([0, 0], [False, True], [4, 0], [3, 3])
    ctx = Context(C, [], 1.0, traced=[r1, r2], trace=tr, trace_lo=0.0,
                  trace_hi=10.0, peaks=pk)
    # leaf ops busy 1 + 3.7 + 2.2 = 6.9 of 10 s
    assert _metric("device_idle_share").read(ctx) == pytest.approx(31.0)
    # the two loops ran 4 steps each (by the kernel's events), 6.0 s in all
    assert _metric("decode_ms_per_step").read(ctx) == pytest.approx(
        1e3 * 6.0 / (4 + 4))
    rows = r1.decode_rows + r2.decode_rows
    assert rows == [(3, 4), (3, 4), (3, 4), (3, 0)]
    f, b = 0.0, 0.0
    for r in (r1, r2):
        rf, rb = flops.decode_attention_cost(C, r.decode_rows)
        f, b = f + rf, b + rb
    want = 100 * max(f / 197e12, b / 819e9) / 2.0
    assert _metric("decode_attention_roofline").read(ctx) == \
        pytest.approx(want)
    nbytes = flops.decode_bytes(C, 4, r1.decode_rows) + \
        flops.decode_bytes(C, 4, r2.decode_rows)
    assert _metric("decode_hbm_share").read(ctx) == pytest.approx(
        100 * nbytes / 819e9 / 6.0)
    # no trace: the device readers find nothing and return nothing
    empty = Context(C, [], 1.0)
    for name in ("device_idle_share", "decode_ms_per_step",
                 "decode_hbm_share", "decode_attention_roofline"):
        assert _metric(name).read(empty) is None


def test_breakdown_groups_ops_and_labels_idle_gaps():
    from harness.cell import breakdown
    bd = breakdown(synthetic_trace(), 0.0, 10.0)
    assert bd["device_ops"][0] == ["copy.125", pytest.approx(2.7)]
    idle = dict(bd["idle_gaps"])
    # idle: [1, 1.2], [4.9, 6.6] (midpoint 5.75, past np.asarray), [8.8, 10]
    assert idle == {"bench.batch": pytest.approx(0.2 + 1.7 + 1.2)}


# ----------------------------------------------------------------- peaks


def test_peaks_of_v5e_and_unknown_kind_is_an_error():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


# ----------------------------------------------------------------- flops


C = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
     "head_dim": 2, "intermediate_size": 8, "num_hidden_layers": 3,
     "vocab_size": 10}


def test_matmul_params_hand_count():
    # per layer: q 4*4 + k,v 2*(4*2) + o 4*4 + mlp 3*4*8 = 16+16+16+96 = 144
    assert flops.matmul_params(C) == 3 * 144 + 10 * 4


def test_forward_flops_hand_count():
    # T=3: 2*472*3 matmul + 3 layers * 4*H*hd*(1+2+3) = 2832 + 3*4*2*2*6
    assert flops.forward_flops(C, 3) == 2 * 472 * 3 + 3 * 4 * 2 * 2 * 6


def test_decode_bytes_and_attention_hand_count():
    rows = [(5, 2), (3, 0), (1, 1)]
    # live kv tokens: row0 steps attend 6, 7; row2 attends 2 -> 15
    assert flops.live_kv_tokens(rows) == 15
    kv_tok = 3 * 2 * 1 * 2 * 2                      # L*2*Hkv*hd*bf16 = 24
    w = 2 * (472 + 3 * (2 * 4 + 2 * 2) + 4)
    assert flops.decode_bytes(C, 2, rows) == 2 * w + kv_tok * 15
    f, b = flops.decode_attention_cost(C, rows)
    assert f == 3 * 4 * 2 * 2 * 15
    assert b == kv_tok * 15 + 3 * 3 * 2 * 2 * 2 * 2


def _record(n, full, length, prompt_len):
    B = len(length)
    P = 8
    mask = np.zeros((B, P), bool)
    for b, p in enumerate(prompt_len):
        mask[b, P - p:] = True
    z = np.zeros((B, 16))
    return Record(0, 0.0, 1.0, {"one_pass": 1.0}, z.astype(np.int32), mask,
                  z.astype(np.int32), z, np.asarray(length), np.asarray(n),
                  np.asarray(full), None, None, 0, 0)


def test_mfu_counts_reused_and_decoded_tokens_alike():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    mfu = load_module(os.path.join(here, "metrics", "mfu.py"), "t_mfu")
    pk = peaks.peaks_for("TPU v5 lite")
    # the same rows, once all decoded and once mostly reused
    fresh = _record([0, 0], [False, False], [12, 16], [5, 5])
    reused = _record([10, 16], [False, True], [12, 16], [5, 5])
    a = mfu.read(Context(C, [fresh], 1.0, peaks=pk))
    b = mfu.read(Context(C, [reused], 1.0, peaks=pk))
    assert a == b == pytest.approx(
        100 * (flops.forward_flops(C, 17) + flops.forward_flops(C, 21))
        / 197e12)


def test_row_util_counts_straggler_steps():
    util = _metric("decode_row_util")
    rec = _record([2, 10, 16], [False, False, True], [16, 16, 16], [4, 4, 4])
    # the loop ran 14 steps of C's 3 layers: 42 kernel events
    mods = [ev("jit_resume_from_cache(1)", 0.0, 2.0)]
    loops = [ev("%while.41 = (s32[]) while(...)", 0.1, 1.9)]
    tr = trace.Trace(ops={0: kernel_events("%decode_attention.5 = f32[8] "
                                           "custom-call(...)", 0.2, 1.8, 42)},
                     loops={0: loops}, modules={0: mods}, host=[])
    ctx = Context(C, [], 1.0, traced=[rec], trace=tr, trace_lo=0.0,
                  trace_hi=2.0)
    # generated 14 + 6 + 0 = 20 over 3 rows x 14 steps
    assert util.read(ctx) == pytest.approx(100 * 20 / 42)
    # a loop that ran 2 steps past the slowest row reads lower
    tr.ops[0] = kernel_events("%decode_attention.5 = f32[8] custom-call(x)",
                              0.2, 1.8, 48)
    assert util.read(ctx) == pytest.approx(100 * 20 / 48)
    # without a trace, nothing
    assert util.read(Context(C, [rec], 1.0)) is None


def test_run_off_the_chip_exits_nonzero_and_prints_no_result():
    import subprocess
    import sys
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    root = os.path.dirname(os.path.dirname(here))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(here, "run.py"),
                        "--workload", "q06b-spec-reuse", "--seed",
                        str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs 1 TPU" in p.stderr
