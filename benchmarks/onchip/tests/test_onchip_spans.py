"""Readers of the program's own spans and counters: the span reduction
and its metrics on a synthetic trace with host spans, the counters'
readers on synthetic records, and the program's decode-step count against
the steps each tiny run's planned work takes (CPU)."""
import os
import time

import numpy as np
import pytest

import onchip_tiny as T
from harness import cell as C
from harness import spans, trace
from harness.cell import Context, Record, load_module

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ev(name, s, e):
    return trace.Event(name, s, e)


def _metric(name):
    return load_module(os.path.join(HERE, "metrics", name + ".py"),
                       "t_spans_" + name)


def span_trace(with_spans=True):
    """One reuse batch: the harness's span, the program's collect span
    and its device stages, and leaf ops with idle gaps in each."""
    ops = [ev("%fusion.1 = f32[8] fusion(x)", 1.0, 1.9),
           ev("%copy.2 = bf16[8] copy(x)", 2.0, 2.4),
           ev("%decode_attention.5 = f32[8] custom-call(x)", 2.7, 7.9),
           ev("%fusion.3 = s32[8] fusion(x)", 8.05, 8.2),
           ev("%reduce.4 = s32[] reduce(x)", 9.0, 9.2)]
    host = [ev("bench.batch", 0.0, 10.0)]
    if with_spans:
        host += [ev("trainer.collect", 0.5, 9.5),
                 ev("rollout.rollout", 0.6, 9.4),
                 ev("rollout.verify", 1.0, 2.0),
                 ev("rollout.compact", 2.0, 2.5),
                 ev("rollout.decode", 2.6, 8.0),
                 ev("rollout.assembly", 8.0, 8.2),
                 ev("rollout.cache_put", 8.3, 8.9),
                 ev("trainer.reward", 9.3, 9.4)]
    return trace.Trace(ops={0: ops}, loops={0: []}, modules={0: []},
                       host=host)


def test_overlap_of_interval_sets():
    assert spans.overlap_s([(0, 2), (3, 5)], [(1, 4)]) == pytest.approx(2)
    assert spans.overlap_s([(0, 1)], [(1, 2)]) == 0
    assert spans.overlap_s([(0, 4), (1, 2)], [(0, 1), (3, 9)]) == \
        pytest.approx(2)


def test_idle_split_puts_every_idle_second_somewhere():
    tr = span_trace()
    split = spans.idle_split(tr, 0.0, 10.0)
    # idle in stages: 1.9-2.0, 2.4-2.5, 2.6-2.7, 7.9-8.05
    assert split["device_stages"] == pytest.approx(0.45)
    # elsewhere in collect: 0.5-1.0, 2.5-2.6, 8.2-9.0, 9.2-9.5
    assert split["host_stages"] == pytest.approx(1.7)
    # the harness: 0-0.5 and 9.5-10
    assert split["outside_collect"] == pytest.approx(1.0)
    idle = sum(e - s for s, e in trace.gaps(tr.ops[0], 0.0, 10.0))
    assert sum(split.values()) == pytest.approx(idle)


def test_span_readers_on_a_synthetic_trace():
    ctx = Context(T.ENTRY["config"], [], 1.0, trace=span_trace(),
                  trace_lo=0.0, trace_hi=10.0)
    # 9.0 s of collect minus the stages' union 1.5 + 5.6 s
    assert _metric("collect_host_ms_per_batch").read(ctx) == \
        pytest.approx(1900.0)
    assert _metric("dispatch_idle_ms_per_batch").read(ctx) == \
        pytest.approx(450.0)
    # a program whose tracer was off leaves no spans: nothing to read
    bare = Context(T.ENTRY["config"], [], 1.0, trace=span_trace(False),
                   trace_lo=0.0, trace_hi=10.0)
    none = Context(T.ENTRY["config"], [], 1.0)
    for name in ("collect_host_ms_per_batch", "dispatch_idle_ms_per_batch"):
        assert _metric(name).read(bare) is None
        assert _metric(name).read(none) is None


def _rec(times):
    z = np.zeros((1, 4), np.int32)
    return Record(0, 0.0, 1.0, times, z, z.astype(bool), z, z.astype(float),
                  np.zeros(1), np.zeros(1), np.zeros(1, bool), None, None,
                  0, 0)


def test_counter_readers():
    steps = _metric("decode_steps_per_batch")
    ctx = Context({}, [_rec({"decode_steps": 470}),
                       _rec({"decode_steps": 468})], 1.0)
    assert steps.read(ctx) == pytest.approx(469.0)
    # a program that does not count its decode steps reads nothing
    assert steps.read(Context({}, [_rec({})], 1.0)) is None
    assert steps.read(Context({}, [], 1.0)) is None
    setup = _metric("setup_compile_s")
    ctx = Context({}, [], 1.0)
    assert setup.read(ctx) is None
    ctx.setup_counters = {"compiles.trace_s": 1.0, "compiles.lower_s": 0.5,
                          "compiles.backend_s": 2.0,
                          "compiles.cache_load_s": 0.25,
                          "compiles.backend_count": 9.0}
    assert setup.read(ctx) == pytest.approx(3.75)
    ctx.setup_counters = {"rollout.step_s_sum": 4.0}
    assert setup.read(ctx) is None


@pytest.mark.parametrize("mix", ["reuse", "fresh"])
def test_decode_steps_are_the_steps_the_planned_work_takes(mix):
    """On the reuse path the loop runs until the row with the most tokens
    left to generate is done; on the fresh path that is every row's whole
    budget, since at this seed no row samples EOS."""
    spec = T.REUSE if mix == "reuse" else T.FRESH
    s = C.Setup(T.ENTRY, spec, 2 ** 31 + 11)
    recs = [C.collect(s.collector, s.params, s.traffic.batch(i),
                      time.perf_counter) for i in range(2)]
    for r in recs:
        assert r.times["decode_steps"] == r.steps
        if mix == "fresh":
            assert r.steps == s.traffic.N
        else:
            assert r.times["one_pass"] == 1.0 and r.steps < s.traffic.N
