"""Helpers shared by the readers of the program's own spans (not a metric
itself).

Ring readers take the records of ``repro.spans`` (the program under test
keeps them in memory) and give each window cycle the records that start
at or after its arrival and before the next cycle's; the warm-up cycle
comes before the first arrival and drops out. Trace readers take the same
spans from the profiler's host events, named as the program names them
(the profiler may append ``#`` and the span's attrs), on the device
trace's clock. A program without spans gives nothing to read: each helper
then returns None or an empty list, and never raises.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench.lib import trace as tr

BUILD_NS = ("trace_ns", "lower_ns", "compile_ns")


def ring() -> Optional[list]:
    """The program's span records, oldest first, or None where the
    program keeps none."""
    try:
        from repro import spans
    except ImportError:
        return None
    return spans.records() or None


def by_cycle(records: Sequence, cycles: Sequence) -> List[list]:
    """The records of each cycle: those that start at or after its
    ``arrival`` (host seconds on ``time.perf_counter``) and before the
    next cycle's."""
    bounds = [c.arrival * 1e9 for c in cycles]
    out: List[list] = [[] for _ in cycles]
    for r in records:
        i = bisect.bisect_right(bounds, r.start_ns) - 1
        if i >= 0:
            out[i].append(r)
    return out


def children(records: Sequence) -> Dict[int, list]:
    kids: Dict[int, list] = defaultdict(list)
    for r in records:
        if r.parent is not None:
            kids[r.parent].append(r)
    return kids


def subtree(root, kids: Dict[int, list]) -> list:
    """``root`` and every record opened inside it."""
    out, todo = [], [root]
    while todo:
        r = todo.pop()
        out.append(r)
        todo.extend(kids.get(r.index, ()))
    return out


def total(records: Sequence, keys: Sequence[str]) -> float:
    return sum(r.counters.get(k, 0) for r in records for k in keys)


def closed(records: Sequence, name: str) -> list:
    return [r for r in records if r.name == name and r.end_ns is not None]


# ------------------------------------------------------------- the trace
def host_intervals(trace, name: str) -> List[Tuple[float, float]]:
    """The union, inside the traced steps, of the host events of one
    program span."""
    evs = [e for e in trace.host if e.name.split("#", 1)[0] == name]
    return tr.busy_intervals(evs, trace.lo, trace.hi)


def idle_ns(trace, intervals: Sequence[Tuple[float, float]]) -> float:
    """Nanoseconds of ``intervals`` (disjoint, sorted) in which no
    operation ran on the device."""
    busy = tr.busy_intervals(trace.events, trace.lo, trace.hi)
    starts = [s for s, _ in busy]
    idle = 0.0
    for s, t in intervals:
        covered = 0.0
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(busy) and busy[i][0] < t:
            covered += max(0.0, min(t, busy[i][1]) - max(s, busy[i][0]))
            i += 1
        idle += (t - s) - covered
    return idle
