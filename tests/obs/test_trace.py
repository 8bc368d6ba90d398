"""Tracer unit tests (DESIGN.md §11): fake-clock determinism, span
nesting/depth bookkeeping, the bounded ring buffer, and the deterministic
per-request sampling hash."""
import pytest

from repro.obs import NULL_TRACER, Tracer


class FakeClock:
    """Monotonic fake clock: each read advances by ``tick``."""

    def __init__(self, tick=1.0):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


def test_span_nesting_and_depth():
    tr = Tracer(clock=FakeClock())
    with tr.span("outer", "main"):
        with tr.span("inner", "main"):
            pass
        with tr.span("inner2", "main"):
            pass
    spans = {s.name: s for s in tr.spans}
    assert spans["outer"].depth == 0
    assert spans["inner"].depth == 1 and spans["inner2"].depth == 1
    # closed in order: inner, inner2, outer
    assert [s.name for s in tr.spans] == ["inner", "inner2", "outer"]
    assert spans["outer"].t0 < spans["inner"].t0
    assert spans["outer"].t1 > spans["inner2"].t1


def test_fake_clock_determinism():
    def run():
        tr = Tracer(clock=FakeClock(0.5))
        h = tr.begin("a", "t1", x=1)
        tr.event("ev", "t1")
        tr.end(h, y=2)
        tr.complete("c", "t2", 10.0, 11.0)
        return [(s.name, s.track, s.t0, s.t1, s.depth, dict(s.args))
                for s in tr.spans] + \
               [(e.name, e.track, e.ts) for e in tr.events]

    assert run() == run()               # byte-for-byte deterministic
    tr = Tracer(clock=FakeClock(0.5))
    h = tr.begin("a", "t1")
    tr.end(h)
    (sp,) = tr.spans
    assert (sp.t0, sp.t1) == (0.5, 1.0)


def test_complete_and_event_explicit_timestamps():
    tr = Tracer(clock=FakeClock())
    tr.complete("stage", "lane", 3.0, 4.5, cat="x", foo="bar")
    tr.event("fault", "lane", ts=3.25)
    (sp,) = tr.spans
    assert (sp.t0, sp.t1, sp.dur) == (3.0, 4.5, 1.5)
    assert sp.args == {"foo": "bar"}
    (ev,) = tr.events
    assert ev.ts == 3.25                # no clock read when ts is given


def test_ring_buffer_bounds_and_drop_count():
    tr = Tracer(clock=FakeClock(), capacity=4)
    for i in range(10):
        tr.complete(f"s{i}", "t", float(i), float(i) + 0.5)
        tr.event(f"e{i}", "t", ts=float(i))
    assert len(tr.spans) == 4 and len(tr.events) == 4
    assert tr.dropped_spans == 6 and tr.dropped_events == 6
    assert [s.name for s in tr.spans] == ["s6", "s7", "s8", "s9"]


def test_disabled_tracer_records_nothing_and_reads_no_clock():
    reads = []

    def clock():
        reads.append(1)
        return 0.0

    tr = Tracer(enabled=False, clock=clock)
    h = tr.begin("a")
    assert h == -1
    tr.end(h)
    with tr.span("b"):
        pass
    tr.complete("c", "t", 0.0, 1.0)
    tr.event("d")
    assert not tr.spans and not tr.events
    assert reads == []                  # the zero-overhead contract
    assert not tr.sampled(0) and not tr.sampled(123)


def test_null_tracer_is_disabled():
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.begin("x") == -1


@pytest.mark.parametrize("rate", [0.0, 0.25, 0.5, 1.0])
def test_sampling_deterministic_and_roughly_proportional(rate):
    tr1 = Tracer(clock=FakeClock(), sample_rate=rate)
    tr2 = Tracer(clock=FakeClock(), sample_rate=rate)
    ids = range(1000)
    picks1 = [tr1.sampled(i) for i in ids]
    picks2 = [tr2.sampled(i) for i in ids]
    assert picks1 == picks2             # shard-invariant decision
    frac = sum(picks1) / 1000
    assert abs(frac - rate) < 0.1       # Knuth hash spreads uniformly


def test_tracks_enumeration_and_clear():
    tr = Tracer(clock=FakeClock())
    tr.complete("a", "engine", 0.0, 1.0)
    tr.complete("b", "req/3", 1.0, 2.0)
    tr.event("c", "req/7")
    assert tr.tracks() == ["engine", "req/3", "req/7"]
    tr.clear()
    assert tr.tracks() == [] and tr.dropped_spans == 0


def test_unbalanced_end_is_harmless():
    tr = Tracer(clock=FakeClock())
    tr.end(999)                         # never-opened handle: no-op
    h = tr.begin("a")
    tr.end(h)
    tr.end(h)                           # double-end: no-op
    assert len(tr.spans) == 1


class FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs enter/exit."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        FakeAnnotation.log.append(("enter", self.name))

    def __exit__(self, *exc):
        FakeAnnotation.log.append(("exit", self.name))


def test_enabled_span_enters_and_leaves_a_profiler_annotation(monkeypatch):
    import jax.profiler
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    FakeAnnotation.log = []
    tr = Tracer(clock=FakeClock())
    with tr.span("collect", "trainer"):
        h = tr.begin("verify", "rollout")
        tr.end(h)
    assert FakeAnnotation.log == [("enter", "trainer.collect"),
                                  ("enter", "rollout.verify"),
                                  ("exit", "rollout.verify"),
                                  ("exit", "trainer.collect")]
    # an after-the-fact span never reaches the profiler
    tr.complete("request", "req/1", 0.0, 1.0)
    assert len(FakeAnnotation.log) == 4


def test_spans_record_parent_and_inherit_the_batch_id():
    tr = Tracer(clock=FakeClock())
    with tr.span("collect", "trainer", batch=7):
        with tr.span("rollout", "rollout", step=3):
            with tr.span("verify", "rollout"):
                assert tr.current().name == "verify"
        with tr.span("reward", "trainer", batch=8):     # own id wins
            pass
    with tr.span("other", "trainer"):
        pass
    sp = {s.name: s for s in tr.spans}
    assert sp["collect"].parent is None and sp["other"].parent is None
    assert sp["verify"].args == {"batch": 7}
    assert sp["rollout"].args == {"step": 3, "batch": 7}
    assert sp["reward"].args == {"batch": 8}
    assert "batch" not in sp["other"].args
    # handles count up in open order: collect 0, rollout 1, verify 2,
    # reward 3; a parent is the enclosing span's handle, across tracks
    assert (sp["rollout"].parent, sp["verify"].parent,
            sp["reward"].parent) == (0, 1, 0)
    assert tr.current() is None


def test_disabled_tracer_imports_nothing(monkeypatch):
    import builtins
    seen = []
    real = builtins.__import__

    def spy(name, *a, **kw):
        seen.append(name)
        return real(name, *a, **kw)

    tr = Tracer(enabled=False)
    monkeypatch.setattr(builtins, "__import__", spy)
    h = tr.begin("a", "t")
    tr.end(h)
    with tr.span("b", "t"):
        pass
    tr.complete("c", "t", 0.0, 1.0)
    monkeypatch.undo()
    assert seen == [] and tr.current() is None


def test_spans_land_in_the_profilers_host_plane(tmp_path):
    """A real profiler trace holds each scoped span as a host event named
    ``<track>.<name>``, nested as the spans were."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((8,))
    f(x).block_until_ready()
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    with tr.span("collect", "trainer", batch=1):
        with tr.span("verify", "rollout"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    got = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("trainer.collect", "rollout.verify"):
                        got[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    assert set(got) == {"trainer.collect", "rollout.verify"}
    (c0, c1), (v0, v1) = got["trainer.collect"], got["rollout.verify"]
    assert c0 <= v0 < v1 <= c1
