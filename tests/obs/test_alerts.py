"""§14 alert rules, recompile sentinel, and device/pool gauges."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import MetricsRegistry, Tracer
from repro.obs.alerts import (SEV_CRIT, AlertManager, AlertRule,
                              compile_counts, default_rules, jit_cache_size,
                              record_compile_gauges, record_device_memory,
                              register_jit_entry)

# ------------------------------------------------------------------ rules


def test_threshold_rule_edge_triggered():
    am = AlertManager([AlertRule("low", "x", "below", 0.5)])
    fired = []
    for v in (1.0, 0.4, 0.3, 0.6, 0.2):
        fired.append(len(am.evaluate({"x": v})))
    # fires once entering the bad region, re-arms after clearing, fires again
    assert fired == [0, 1, 0, 0, 1]
    assert am.as_dict()["alerts_fired"] == 2.0


def test_warmup_suppresses_early_samples():
    am = AlertManager([AlertRule("low", "x", "below", 0.5, warmup=3)])
    assert not am.evaluate({"x": 0.0})
    assert not am.evaluate({"x": 0.0})
    assert not am.evaluate({"x": 0.0})
    assert len(am.evaluate({"x": 0.0})) == 1


def test_trend_rule_needs_full_window():
    am = AlertManager([AlertRule("up", "x", "trend_up", 0.0, window=4)])
    events = []
    for v in (1.0, 2.0, 3.0, 4.0):        # monotone rise across the window
        events += am.evaluate({"x": v})
    assert [e.rule for e in events] == ["up"]
    # flat history clears and re-arms
    for v in (4.0, 4.0, 4.0, 4.0):
        events += am.evaluate({"x": v})
    assert len(events) == 1


def test_missing_metric_is_inert():
    am = AlertManager(default_rules())
    for _ in range(20):
        assert am.evaluate({"loss": 1.0}) == []


def test_events_route_to_tracer_and_watchdog():
    class Dog:
        def __init__(self):
            self.got = []

        def note_alert(self, ev):
            self.got.append(ev)

    tr = Tracer(enabled=True)
    dog = Dog()
    am = AlertManager([AlertRule("boom", "x", "above", 0.0,
                                 severity=SEV_CRIT, message="m")],
                      tracer=tr, watchdog=dog)
    evs = am.evaluate({"x": 1.0}, step=7)
    assert len(evs) == 1 and evs[0].step == 7 and evs[0].severity == SEV_CRIT
    assert [e.name for e in tr.events] == ["alert/boom"]
    assert tr.events[0].args["value"] == 1.0
    assert dog.got == evs


def test_trainwatchdog_note_alert_counts(tmp_path):
    from repro.rl.watchdog import TrainWatchdog, WatchdogConfig
    wd = TrainWatchdog(WatchdogConfig(checkpoint_dir=str(tmp_path)))
    am = AlertManager([AlertRule("boom", "x", "above", 0.0,
                                 severity=SEV_CRIT)], watchdog=wd)
    am.evaluate({"x": 1.0})
    assert wd.alert_events == 1 and wd.crit_alert_events == 1
    assert wd.last_alert == "boom"
    assert wd.as_dict()["watchdog_crit_alert_events"] == 1.0


def test_default_rules_fire_on_canned_collapse():
    am = AlertManager(default_rules())
    fired = []
    for step in range(8):
        m = {"accept_rate": 0.5 if step < 6 else 0.01,
             "paged_alloc_failures": 0.0 if step < 7 else 2.0}
        fired += am.evaluate(m, step=step)
    names = {e.rule for e in fired}
    assert names == {"draft_accept_collapse", "pool_alloc_failures"}


# ------------------------------------------------------- recompile sentinel


def test_jit_cache_size_counts_signatures():
    @jax.jit
    def f(x):
        return x + 1

    n0 = jit_cache_size(f)
    if n0 is None:
        pytest.skip("jax build exposes no _cache_size probe")
    f(jnp.zeros(2))
    f(jnp.zeros(2))                       # same signature: no new compile
    assert jit_cache_size(f) == n0 + 1
    f(jnp.zeros(3))                       # new shape: one more
    assert jit_cache_size(f) == n0 + 2


def test_registered_entries_feed_compile_gauges():
    @jax.jit
    def g(x):
        return x * 2

    register_jit_entry("test_entry_g", g)
    try:
        g(jnp.zeros(4))
        counts = compile_counts()
        if "test_entry_g" not in counts:
            pytest.skip("jax build exposes no _cache_size probe")
        assert counts["test_entry_g"] >= 1
        reg = MetricsRegistry()
        record_compile_gauges(reg)
        d = reg.as_dict()
        assert d["compiles.test_entry_g"] >= 1.0
        assert d["compiles.total"] >= d["compiles.test_entry_g"]
    finally:
        from repro.obs.alerts import _JIT_ENTRIES
        _JIT_ENTRIES.pop("test_entry_g", None)


def test_engine_modules_enroll_their_entries():
    import repro.core.verify           # noqa: F401
    import repro.drafting.step         # noqa: F401
    import repro.serving.engine_loop   # noqa: F401
    from repro.obs.alerts import _JIT_ENTRIES
    assert {"draft_step", "verify_drafts", "verify_and_prefill",
            "decode_chunk"} <= set(_JIT_ENTRIES)


def test_recompile_rule_fires_on_cache_growth():
    rules = [r for r in default_rules()
             if r.name == "recompile_steady_state"]
    am = AlertManager(rules)
    evs = []
    # warmup growth ignored, then steady ... then growth again
    for total in (1, 2, 3, 4, 4, 4, 4, 4):
        evs += am.evaluate({"compiles.total": float(total)})
    assert evs == []
    for total in (5, 6, 7, 8):
        evs += am.evaluate({"compiles.total": float(total)})
    assert [e.rule for e in evs] == ["recompile_steady_state"]


# ---------------------------------------------------------------- gauges


def test_compile_listener_counts_a_recompile_on_the_open_span():
    """``configure`` installs the compile-seconds listener once per
    process; a forced recompile adds to the ``compiles.*`` counters and
    to the innermost open span's args."""
    from repro.obs import configure, get_registry, reset
    try:
        tr = Tracer()
        configure(tracer=tr, registry=MetricsRegistry())
        configure(registry=MetricsRegistry())       # installs only once
        f = jax.jit(lambda x: x * 3 + 1)
        f(jnp.ones((3,))).block_until_ready()
        x5 = jnp.ones((5,))
        before = get_registry().as_dict()
        with tr.span("collect", "trainer"):
            with tr.span("decode", "rollout"):
                f(x5).block_until_ready()                # new shape
        after = get_registry().as_dict()
    finally:
        reset()
    for kind in ("trace", "lower", "backend"):
        assert after[f"compiles.{kind}_count"] > \
            before[f"compiles.{kind}_count"]
        assert after[f"compiles.{kind}_s"] > before[f"compiles.{kind}_s"]
    sp = {s.name: s for s in tr.spans}
    assert sp["decode"].args["compile.backend_s"] > 0
    assert not any(k.startswith("compile.") for k in sp["collect"].args)


def test_compile_seconds_take_a_cache_load_off_its_backend_event():
    from repro.obs import get_registry, reset
    from repro.obs.alerts import COMPILE_EVENTS, CompileSeconds
    ev = {kind: name for name, kind in COMPILE_EVENTS.items()}
    listen = CompileSeconds()
    try:
        reset()
        listen(ev["cache_load"], 0.25)       # inside the backend event
        listen(ev["backend"], 0.5)
        listen(ev["backend"], 2.0)           # a compile: no load inside
        listen("/jax/other/event", 9.0)      # not a compile event
        d = get_registry().as_dict()
    finally:
        reset()
    assert d["compiles.cache_load_s"] == 0.25
    assert d["compiles.backend_s"] == 0.25 + 2.0
    assert d["compiles.backend_count"] == 2.0
    assert not any("other" in k for k in d)


def test_record_device_memory_never_raises():
    reg = MetricsRegistry()
    record_device_memory(reg)            # CPU: memory_stats() is None/empty
    d = reg.as_dict()
    for k in d:
        if k.startswith("device."):
            assert np.isfinite(d[k])


def test_paged_pool_gauges_exported():
    from repro.engine.generate import GenerateConfig
    from repro.models import model as M
    from repro.models.config import ModelConfig
    from repro.serving import Request
    from repro.serving.paged_engine import PagedSlotEngine

    cfg = ModelConfig(name="t", num_layers=2, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=32,
                      cache_layout="paged", kv_block_size=8)
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    gen = GenerateConfig(max_new_tokens=4)
    eng = PagedSlotEngine(params, cfg, gen, num_slots=2, prompt_width=8,
                          chunk_steps=2)
    rng = np.random.RandomState(0)
    keys = np.asarray(jax.vmap(lambda i: jax.random.fold_in(
        jax.random.PRNGKey(5), i))(jnp.arange(2)))
    for i in range(2):
        eng.submit(Request(request_id=i,
                           prompt=rng.randint(3, 32, 5).astype(np.int32),
                           key=keys[i], max_new_tokens=4))
    eng.run()
    d = eng.metrics_registry().as_dict()
    assert 0.0 <= d["paged_pool_pressure"] <= 1.0
    assert d["paged_bytes_in_use"] >= 0.0
    assert d["paged_peak_bytes_in_use"] > 0.0
