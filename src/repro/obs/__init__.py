"""Rollout observatory: span tracing + unified metrics (DESIGN.md §11).

Components that are constructed explicitly (SlotEngine, MeshSlotServer)
take a ``tracer=`` kwarg; code deep in the loop (spec_rollout, the drafted
decode loop, the trainer) reads the process-global tracer/registry below,
which launch scripts set once via ``configure`` before building anything.
The defaults (``NULL_TRACER``, an idle registry) satisfy the zero-overhead
contract: every recording call early-returns.
"""
from .trace import NULL_TRACER, Event, Span, Tracer
from .registry import (Counter, Gauge, Histogram, MetricsRegistry, Ratio,
                       extend_summary)
from .ledger import (NULL_DECISION_LOG, NULL_LEDGER, DecisionLog,
                     LedgerError, TokenLedger)
from . import export  # noqa: F401  (re-exported submodule)

_TRACER: Tracer = NULL_TRACER
_REGISTRY: MetricsRegistry = MetricsRegistry()
_LEDGER: TokenLedger = NULL_LEDGER
_DECISIONS: DecisionLog = NULL_DECISION_LOG


def get_tracer() -> Tracer:
    return _TRACER


def get_registry() -> MetricsRegistry:
    return _REGISTRY


def get_ledger() -> TokenLedger:
    return _LEDGER


def get_decision_log() -> DecisionLog:
    return _DECISIONS


def configure(tracer: Tracer = None,
              registry: MetricsRegistry = None,
              ledger: TokenLedger = None,
              decisions: DecisionLog = None) -> None:
    """Install process-global observability sinks (launch scripts), and
    the compile-seconds listener (once per process, obs/alerts.py)."""
    global _TRACER, _REGISTRY, _LEDGER, _DECISIONS
    from .alerts import install_compile_listener
    install_compile_listener()
    if tracer is not None:
        _TRACER = tracer
    if registry is not None:
        _REGISTRY = registry
    if ledger is not None:
        _LEDGER = ledger
    if decisions is not None:
        _DECISIONS = decisions


def reset() -> None:
    """Back to the inert defaults (tests)."""
    global _TRACER, _REGISTRY, _LEDGER, _DECISIONS
    _TRACER = NULL_TRACER
    _REGISTRY = MetricsRegistry()
    _LEDGER = NULL_LEDGER
    _DECISIONS = NULL_DECISION_LOG


__all__ = ["Tracer", "Span", "Event", "NULL_TRACER",
           "MetricsRegistry", "Counter", "Gauge", "Histogram", "Ratio",
           "extend_summary", "export",
           "TokenLedger", "LedgerError", "NULL_LEDGER",
           "DecisionLog", "NULL_DECISION_LOG",
           "get_tracer", "get_registry", "get_ledger", "get_decision_log",
           "configure", "reset"]
