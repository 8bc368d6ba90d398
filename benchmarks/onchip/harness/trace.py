"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

``load`` reads the trace with JAX's own ``ProfileData`` into plain event
lists: per device, the op events of its ``XLA Ops`` line and the program
events of its ``XLA Modules`` line; and every host event.  Everything after
that is arithmetic on (start, end) intervals in seconds, kept free of JAX so
it can be tested on synthetic intervals:

* ``union`` / ``busy_s``: the union of op intervals, so overlapping ops
  count once;
* ``gaps``: the idle intervals of the window, each labelled by the innermost
  host event that spans its midpoint (what the host was doing meanwhile);
* ``time_by_name``: device seconds per event name, with a name filter, for a
  kernel's time;
* ``decode_loops``: the span of each decode ``while`` loop, the longest
  ``while`` op inside each run of a decode program;
* ``decode_steps``: the trip count of each decode loop, read from the
  decode attention kernel's events inside it (one per layer and step);
* ``whole_loops``: whether the trace holds every decode loop whole.

On a TPU the ``XLA Ops`` line holds control-flow ops (``while``,
``conditional``, ``call``) as events that enclose their bodies' ops; busy
time and op time count only the leaf ops.  Op events are named by their HLO
instruction text (``%decode_attention.5 = (f32[...]) custom-call(...)``);
``short_name`` keeps the instruction's name (``decode_attention.5``).
Host and device events share the trace's clock.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTROL_FLOW = ("while", "conditional", "call")
# the programs whose main loop is the decode loop: engine/generate.py's
# ``generate`` (prefill, then the loop) and ``resume_from_cache`` (the loop)
DECODE_PROGRAMS = ("jit_generate", "jit_resume_from_cache")
# the kernel every decode step runs once per layer (kernels/decode_attention)
DECODE_KERNEL = "decode_attention"


@dataclass
class Event:
    name: str
    start: float                 # seconds
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``; a program
    ``jit_generate(1234)`` -> ``jit_generate``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return head.split("(", 1)[0]


def is_leaf(ev: Event) -> bool:
    return not short_name(ev.name).startswith(CONTROL_FLOW)


@dataclass
class Trace:
    ops: Dict[int, List[Event]]          # device index -> leaf op events
    loops: Dict[int, List[Event]]        # device index -> control-flow ops
    modules: Dict[int, List[Event]]      # device index -> program events
    host: List[Event]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        s = e.start_ns * 1e-9
        out.append(Event(e.name, s, s + e.duration_ns * 1e-9))
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace({}, {}, {}, [])
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs = _events(line)
                    tr.ops[dev] = [e for e in evs if is_leaf(e)]
                    tr.loops[dev] = [e for e in evs if not is_leaf(e)]
                elif line.name == MODULES_LINE:
                    tr.modules[dev] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend(e for e in _events(line) if e.dur > 0)
    return tr


# ------------------------------------------------------------ interval math


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_s(events: Sequence[Event], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which at least one event runs."""
    return sum(e - s for s, e in clip(union((ev.start, ev.end)
                                            for ev in events), lo, hi))


def gaps(events: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of [lo, hi]: where no event runs."""
    out, t = [], lo
    for s, e in clip(union((ev.start, ev.end) for ev in events), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


SHORT_GAP = 50e-6


def label_gaps(idle: Sequence[Tuple[float, float]], host: Sequence[Event],
               unknown: str = "(no host event)") -> Dict[str, float]:
    """Idle seconds by the innermost host event spanning each gap's
    midpoint.  Gaps under SHORT_GAP (launch to launch inside a program) are
    summed under one label rather than looked up."""
    by: Dict[str, float] = defaultdict(float)
    spans = sorted(host, key=lambda e: e.start)
    for s, e in idle:
        if e - s < SHORT_GAP:
            by["(gaps under 50 us)"] += e - s
            continue
        mid = 0.5 * (s + e)
        inner: Optional[Event] = None
        for ev in spans:
            if ev.start > mid:
                break
            if ev.end >= mid and (inner is None or ev.dur < inner.dur):
                inner = ev
        by[inner.name if inner else unknown] += e - s
    return dict(by)


def time_by_name(events: Sequence[Event], lo: float, hi: float,
                 keep: Callable[[Event], bool] = lambda e: True
                 ) -> Dict[str, float]:
    """Device seconds per event name within [lo, hi]."""
    by: Dict[str, float] = defaultdict(float)
    for ev in events:
        if keep(ev):
            s, e = max(ev.start, lo), min(ev.end, hi)
            if e > s:
                by[ev.name] += e - s
    return dict(by)


def decode_loops(tr: Trace, dev: int = 0) -> List[Event]:
    """One event per run of a decode program on ``dev``: its longest
    ``while`` op (the decode loop, which encloses the per-layer scans)."""
    out = []
    loops = [e for e in tr.loops.get(dev, [])
             if short_name(e.name).startswith("while")]
    for mod in sorted(tr.modules.get(dev, []), key=lambda e: e.start):
        if short_name(mod.name) in DECODE_PROGRAMS:
            inside = [e for e in loops
                      if e.start >= mod.start and e.end <= mod.end]
            if inside:
                out.append(max(inside, key=lambda e: e.dur))
    return out


def decode_steps(tr: Trace, layers: int, dev: int = 0,
                 kernel: str = DECODE_KERNEL) -> List[int]:
    """The trip count of each of ``decode_loops``: the kernel's events
    inside the loop over the number of layers.  Empty when a loop holds no
    such event or a count that is not a whole number of steps."""
    kern = [e for e in tr.ops.get(dev, [])
            if short_name(e.name).startswith(kernel)]
    out = []
    for loop in decode_loops(tr, dev):
        k = sum(1 for e in kern if e.start >= loop.start and e.end <= loop.end)
        if k == 0 or k % layers:
            return []
        out.append(k // layers)
    return out


def whole_loops(tr: Trace, need: Sequence[int], layers: int,
                dev: int = 0) -> bool:
    """Whether the trace holds one whole decode loop per traced batch: a
    loop for each, with the kernel's events a whole number of steps and at
    least the ``need`` steps that batch's work takes.  The profiler can drop
    device events (the enclosing ``while`` of a loop among them); a trace
    that lost part of a loop reads nothing true."""
    steps = decode_steps(tr, layers, dev)
    return len(steps) == len(need) and all(s >= n for s, n in
                                           zip(steps, need))


def top(d: Dict[str, float], n: int = 10) -> List[List[object]]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]

