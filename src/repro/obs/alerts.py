"""Metric alert rules + recompile sentinel + memory gauges (DESIGN.md §14).

The §11 registry records everything and judges nothing: a draft-acceptance
collapse or a steady-state recompile storm is invisible until a bench
regresses.  ``AlertManager`` closes that gap with declarative rules
evaluated over registry dumps each training step:

- ``below`` / ``above``: the metric crossed a threshold after ``warmup``
  observations (collapse detectors);
- ``trend_up`` / ``trend_down``: the metric moved monotonically-on-average
  across a sliding ``window`` by more than ``threshold`` (leak/storm
  detectors — pool exhaustion, staleness rise, recompiles).

Firing is edge-triggered: a rule raises one typed ``AlertEvent`` when its
predicate first becomes true and re-arms only after it clears, so a
persistent condition does not spam the trace.  Events land as instants on
the tracer's ``alerts`` track (visible in the Chrome timeline next to the
spans that caused them) and, optionally, route into the §10
``TrainWatchdog`` via ``note_alert`` so the degradation ladder can react.

Recompile sentinel: every jit'd entry point in this repo is a
module-level ``jax.jit`` wrapper, so its internal cache size *is* the
cumulative per-signature compile count for the process.
``register_jit_entry`` enrolls an entry once (import time);
``record_compile_gauges`` snapshots ``compiles.<name>`` gauges into a
registry, which the ``recompile_steady_state`` trend rule then watches.
A healthy engine compiles during warmup and never again — any upward
trend after that is a shape leak.

Compile seconds: ``install_compile_listener`` (called once per process by
``obs.configure``) adds a ``jax.monitoring`` duration listener that sums
the seconds of jaxpr tracing, lowering, backend compilation and
persistent-cache loads into the ``compiles.{trace,lower,backend,
cache_load}_s`` counters of the process-global registry, with a
``compiles.<kind>_count`` beside each, and, when the global tracer is
enabled, onto the innermost open span's args (``compile.<kind>_s``), so
the stage that recompiled is named.  It fires only when JAX compiles.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

from .registry import MetricsRegistry
from .trace import Tracer

SEV_WARN = "warn"
SEV_CRIT = "crit"

_KINDS = ("below", "above", "trend_up", "trend_down")


@dataclass(frozen=True)
class AlertRule:
    """One declarative predicate over a registry metric."""
    name: str                    # rule id (unique within a manager)
    metric: str                  # registry/as_dict key to watch
    kind: str                    # below | above | trend_up | trend_down
    threshold: float
    warmup: int = 0              # observations ignored before arming
    window: int = 8              # trend window (samples)
    severity: str = SEV_WARN
    message: str = ""

    def __post_init__(self):
        assert self.kind in _KINDS, self.kind


@dataclass
class AlertEvent:
    """A rule firing: what tripped, on which value, at which step."""
    rule: str
    metric: str
    value: float
    threshold: float
    step: int
    severity: str = SEV_WARN
    message: str = ""

    def as_args(self) -> Dict[str, Any]:
        return {"rule": self.rule, "metric": self.metric,
                "value": self.value, "threshold": self.threshold,
                "severity": self.severity, "message": self.message}


def default_rules() -> List[AlertRule]:
    """The standing rule set for a SPEC-RL training run.  Rules whose
    metric never appears (e.g. paged gauges on a dense engine) are
    silently inert."""
    return [
        AlertRule("draft_accept_collapse", "accept_rate", "below", 0.05,
                  warmup=5, severity=SEV_WARN,
                  message="draft acceptance collapsed — §9 drafts are "
                          "burning verify forwards for nothing"),
        AlertRule("reuse_collapse", "reuse_rate", "below", 0.05,
                  warmup=5, severity=SEV_WARN,
                  message="SPEC-RL prefix reuse collapsed — policy has "
                          "drifted past the cached rollouts"),
        AlertRule("pool_alloc_failures", "paged_alloc_failures", "above",
                  0.0, severity=SEV_CRIT,
                  message="paged KV pool exhausted — admissions shed"),
        AlertRule("pool_exhaustion_trend", "paged_blocks_in_use",
                  "trend_up", 0.0, warmup=4, window=8,
                  message="live block watermark rising — pool heading "
                          "for exhaustion"),
        AlertRule("staleness_rise", "async.staleness", "trend_up", 0.0,
                  warmup=4, window=8,
                  message="rollout staleness rising — trainer is "
                          "outrunning the producer"),
        AlertRule("recompile_steady_state", "compiles.total", "trend_up",
                  0.0, warmup=4, window=4, severity=SEV_CRIT,
                  message="jit recompiles in steady state — a shape is "
                          "leaking into traced code"),
    ]


DEFAULT_RULES = default_rules()


class AlertManager:
    """Evaluate rules against successive registry dumps.

    ``evaluate`` takes either a ``MetricsRegistry`` or a flat
    ``as_dict()``-style mapping, appends each watched metric to its rule's
    history, and returns the events that fired this step (already emitted
    to the tracer / watchdog).
    """

    def __init__(self, rules: Optional[Sequence[AlertRule]] = None,
                 tracer: Optional[Tracer] = None, watchdog=None):
        self.rules = list(DEFAULT_RULES if rules is None else rules)
        ids = [r.name for r in self.rules]
        assert len(ids) == len(set(ids)), f"duplicate rule ids: {ids}"
        self.tracer = tracer
        self.watchdog = watchdog
        self._hist: Dict[str, deque] = {
            r.name: deque(maxlen=max(2, r.window)) for r in self.rules}
        self._seen: Dict[str, int] = {r.name: 0 for r in self.rules}
        self._active: set = set()
        self.events: List[AlertEvent] = []

    # ------------------------------------------------------------ predicate

    @staticmethod
    def _tripped(rule: AlertRule, hist: deque) -> bool:
        v = hist[-1]
        if rule.kind == "below":
            return v < rule.threshold
        if rule.kind == "above":
            return v > rule.threshold
        if len(hist) < max(2, rule.window):
            return False
        delta = hist[-1] - hist[0]
        return delta > rule.threshold if rule.kind == "trend_up" \
            else delta < -rule.threshold

    def evaluate(self, metrics: Union[MetricsRegistry, Dict[str, float]],
                 step: int = 0) -> List[AlertEvent]:
        flat = metrics.as_dict() if isinstance(metrics, MetricsRegistry) \
            else metrics
        fired: List[AlertEvent] = []
        for rule in self.rules:
            val = flat.get(rule.metric)
            if not isinstance(val, (int, float)):
                continue                       # metric absent: rule inert
            self._seen[rule.name] += 1
            if self._seen[rule.name] <= rule.warmup:
                continue        # warmup samples never enter the window —
                                # compile/pool growth during warmup must not
                                # pre-charge the trend detectors
            hist = self._hist[rule.name]
            hist.append(float(val))
            if self._tripped(rule, hist):
                if rule.name not in self._active:   # edge-trigger
                    self._active.add(rule.name)
                    ev = AlertEvent(rule=rule.name, metric=rule.metric,
                                    value=float(val),
                                    threshold=rule.threshold, step=step,
                                    severity=rule.severity,
                                    message=rule.message)
                    fired.append(ev)
            else:
                self._active.discard(rule.name)     # cleared: re-arm
        for ev in fired:
            self._emit(ev)
        self.events.extend(fired)
        return fired

    def _emit(self, ev: AlertEvent) -> None:
        if self.tracer is not None and self.tracer.enabled:
            self.tracer.event(f"alert/{ev.rule}", "alerts",
                              cat=ev.severity, **ev.as_args())
        if self.watchdog is not None and \
                hasattr(self.watchdog, "note_alert"):
            self.watchdog.note_alert(ev)

    def as_dict(self, prefix: str = "alerts_") -> Dict[str, float]:
        out = {f"{prefix}fired": float(len(self.events)),
               f"{prefix}active": float(len(self._active))}
        for ev in self.events[-8:]:
            out.setdefault(f"{prefix}last_{ev.rule}", float(ev.step))
        return out


# --------------------------------------------------------- recompile sentinel

#: name → jit-wrapped callable, enrolled at import time by the modules that
#: own the entry points (engine_loop, drafting/step, core/verify)
_JIT_ENTRIES: Dict[str, Callable] = {}


def jit_cache_size(fn) -> Optional[int]:
    """Cumulative per-signature compile count of a ``jax.jit`` wrapper, or
    None when this jax build doesn't expose the probe."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:
        return None


def register_jit_entry(name: str, fn) -> None:
    """Enroll a module-level jit entry point for the sentinel.  Idempotent
    by name; harmless when the probe is unavailable."""
    _JIT_ENTRIES[name] = fn


def compile_counts() -> Dict[str, int]:
    """Current compile count per enrolled entry (probe-less entries skipped)."""
    out: Dict[str, int] = {}
    for name, fn in _JIT_ENTRIES.items():
        n = jit_cache_size(fn)
        if n is not None:
            out[name] = n
    return out


def record_compile_gauges(reg: MetricsRegistry) -> None:
    """Snapshot ``compiles.<name>`` gauges plus the ``compiles.total`` the
    recompile rule watches.  agg="max": on a mesh every shard sees the same
    process-global jit caches, so the merge must not double-count."""
    counts = compile_counts()
    if not counts:
        return
    for name, n in counts.items():
        reg.set(f"compiles.{name}", float(n), agg="max")
    reg.set("compiles.total", float(sum(counts.values())), agg="max")


#: jax.monitoring duration events -> compile-seconds kind
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


class CompileSeconds:
    """The duration listener behind ``install_compile_listener``.

    JAX times a persistent-cache load inside the backend-compile event that
    encloses it, so the load's seconds are taken off that event's: the
    four kinds then add up to the compile time without counting a load
    twice."""

    def __init__(self):
        self._loaded = 0.0           # cache-load seconds inside the open
                                     # backend-compile event

    def __call__(self, event: str, duration: float, **kw) -> None:
        kind = COMPILE_EVENTS.get(event)
        if kind is None:
            return
        if kind == "cache_load":
            self._loaded += duration
        elif kind == "backend":
            duration, self._loaded = max(0.0, duration - self._loaded), 0.0
        from . import get_registry, get_tracer
        reg = get_registry()
        reg.inc(f"compiles.{kind}_s", duration)
        reg.inc(f"compiles.{kind}_count")
        sp = get_tracer().current()          # None unless enabled
        if sp is not None:
            key = f"compile.{kind}_s"
            sp.args[key] = sp.args.get(key, 0.0) + duration


_COMPILE_LISTENER: Optional[CompileSeconds] = None


def install_compile_listener() -> None:
    """Register the compile-seconds listener with ``jax.monitoring`` once
    per process (idempotent)."""
    global _COMPILE_LISTENER
    if _COMPILE_LISTENER is None:
        import jax.monitoring
        _COMPILE_LISTENER = CompileSeconds()
        jax.monitoring.register_event_duration_secs_listener(
            _COMPILE_LISTENER)


def record_device_memory(reg: MetricsRegistry) -> None:
    """Live/peak device-memory gauges when the backend reports them
    (``memory_stats()`` is None on CPU — gauges simply don't appear)."""
    try:
        import jax
        dev = jax.local_devices()[0]
        ms = dev.memory_stats() if hasattr(dev, "memory_stats") else None
    except Exception:
        return
    if not ms:
        return
    for src, dst in (("bytes_in_use", "device.bytes_in_use"),
                     ("peak_bytes_in_use", "device.peak_bytes_in_use"),
                     ("bytes_limit", "device.bytes_limit")):
        if src in ms:
            reg.set(dst, float(ms[src]),
                    agg="max" if "peak" in src or "limit" in src else "last")
