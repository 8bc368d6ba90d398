"""The program's own spans in a profiler trace's host plane.

An enabled ``repro.obs`` tracer writes each scoped span of the collection
step into the trace as a host event named ``<track>.<name>``
(``trainer.collect``, ``rollout.verify``), on the clock of the device's op
events.  The device stages (``DEVICE_STAGES``) each end at a
``block_until_ready``: the device should be busy through them, and idle
inside them is the runtime's launch, transfer and sync cost.  The rest of
``trainer.collect`` is the program's host time, and the rest of the traced
window is the harness's.  A trace without these spans (a program whose
tracer was off) reads nothing.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

from . import trace

COLLECT = "trainer.collect"
DEVICE_STAGES = ("rollout.verify", "rollout.compact", "rollout.decode",
                 "rollout.generate", "rollout.assembly")


def named(host: Sequence[trace.Event], names: Sequence[str], lo: float,
          hi: float) -> List[trace.Event]:
    """Host events of the given names lying within [lo, hi]."""
    return [e for e in host
            if e.name in names and e.start >= lo and e.end <= hi]


def overlap_s(a: Sequence[Tuple[float, float]],
              b: Sequence[Tuple[float, float]]) -> float:
    """Seconds covered by both interval sets."""
    ua, ub = trace.union(a), trace.union(b)
    total, j = 0.0, 0
    for s, e in ua:
        while j < len(ub) and ub[j][1] <= s:
            j += 1
        k = j
        while k < len(ub) and ub[k][0] < e:
            total += min(e, ub[k][1]) - max(s, ub[k][0])
            k += 1
    return total


def _intervals(events: Sequence[trace.Event]):
    return [(e.start, e.end) for e in events]


def collect_host_s(tr: trace.Trace, lo: float, hi: float) -> List[float]:
    """Per ``trainer.collect`` span in [lo, hi]: its duration minus the part
    its device-stage spans cover."""
    stages = _intervals(named(tr.host, DEVICE_STAGES, lo, hi))
    return [c.dur - overlap_s([(c.start, c.end)], stages)
            for c in named(tr.host, (COLLECT,), lo, hi)]


def idle_split(tr: trace.Trace, lo: float, hi: float,
               dev: int = 0) -> Dict[str, float]:
    """Device idle seconds of [lo, hi] by where the host was: inside a
    device-stage span, elsewhere inside ``trainer.collect`` (the program's
    host stages), or outside it (the harness)."""
    idle = trace.gaps(tr.ops.get(dev, []), lo, hi)
    total = sum(e - s for s, e in idle)
    in_stages = overlap_s(idle, _intervals(named(tr.host, DEVICE_STAGES,
                                                 lo, hi)))
    in_collect = overlap_s(idle, _intervals(named(tr.host, (COLLECT,),
                                                  lo, hi)))
    return {"device_stages": in_stages, "host_stages": in_collect - in_stages,
            "outside_collect": total - in_collect}
