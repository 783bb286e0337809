"""Reduction of a JAX profiler trace (``.xplane.pb``) to device busy
time, kernel time by name, step spans and the breakdown of the longest
idle gaps by what the host was doing.

Device planes are ``/device:<KIND>:<n>``; their ``XLA Ops`` line holds
one event per operation that ran. Host planes hold the benchmark's own
``TraceAnnotation`` spans (names starting ``bench.``) among the events of
the program and of JAX. All times are nanoseconds on the trace's clock.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"


class Event(NamedTuple):
    name: str          # host: the event's name; device: the HLO
    start: float       # instruction's name and result type
    end: float
    text: str          # the full event name and every string stat


_LAYOUT = re.compile(r"\{[^{}]*\}")


class Trace(NamedTuple):
    ops: Dict[str, List[Event]]      # device plane -> its operations
    host: List[Event]                # every host event, all threads


def find_xplane(logdir: str) -> str:
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {logdir}, "
                                f"found {found}")
    return found[0]


def short_name(hlo: str) -> str:
    """``%fusion.113 = bf16[8,12800]{1,0:...} fusion(...)`` -> ``%fusion.113
    bf16[8,12800]``; a tuple result is written ``(...)``."""
    name, eq, rest = hlo.partition(" = ")
    if not eq:
        return hlo
    result = "(...)" if rest.startswith("(") else \
        _LAYOUT.sub("", rest.split(" ", 1)[0])
    return f"{name} {result}"


def _event(e, device: bool) -> Event:
    parts = [e.name]
    for _, v in e.stats:
        if isinstance(v, str):
            parts.append(v)
    return Event(short_name(e.name) if device else e.name, e.start_ns,
                 e.start_ns + e.duration_ns, "\n".join(parts))


def load(path: str) -> Trace:
    """A trace file, as the profiler wrote it or gzipped (``.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = [_event(e, True) for line in plane.lines
                   if line.name == OPS_LINE for e in line.events]
            ops[plane.name] = sorted(evs, key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            host.extend(_event(e, False) for line in plane.lines
                        for e in line.events)
    return Trace(ops, sorted(host, key=lambda e: e.start))


def spans(trace: Trace, name: str) -> List[Event]:
    """Host events of one name (the benchmark's annotations), in order."""
    return [e for e in trace.host if e.name == name]


def _clip(events: Sequence[Event], lo: float, hi: float):
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            yield e, s, t


def busy_intervals(events: Sequence[Event], lo: float,
                   hi: float) -> List[Tuple[float, float]]:
    """The union of the events' intervals inside [lo, hi]."""
    out: List[List[float]] = []
    for _, s, t in sorted(_clip(events, lo, hi), key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(t - s for s, t in busy_intervals(events, lo, hi))


def time_by_name(events: Sequence[Event], lo: float,
                 hi: float) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    for e, s, t in _clip(events, lo, hi):
        out[e.name] += t - s
    return dict(out)


def leaves(events: Sequence[Event]) -> List[Event]:
    """The operations that hold no other: a loop or call on the device
    spans the operations it runs, which are listed beside it."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt.start >= e.end]


_SUFFIX = re.compile(r"\.\d+$")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_META = "kernel_metadata="


def op_name(text: str) -> str:
    """The own name of a device operation's HLO instruction, without
    ``%`` and the ``.N`` suffix: ``%tpu_custom_call.9 = bf16[8,4]{1,0}
    custom-call(%a)`` -> ``tpu_custom_call``."""
    head = text.partition(" = ")[0].split()
    return _SUFFIX.sub("", head[-1].lstrip("%")) if head else ""


def kernel_metadata(text: str) -> dict:
    """The ``kernel_metadata`` a Pallas call carries among its own
    attributes ({} where it carries none). Operands name other
    instructions and carry no attributes, so the first such attribute in
    an operation's text is its own."""
    i = text.find(_META)
    if i < 0:
        return {}
    try:
        meta, _ = json.JSONDecoder().raw_decode(text, i + len(_META))
    except ValueError:
        return {}
    return meta if isinstance(meta, dict) else {}


def _ops_ns(events: Sequence[Event], lo: float, hi: float,
            match) -> Tuple[float, int]:
    total, n = 0.0, 0
    for e, s, t in _clip(events, lo, hi):
        if match(e.text):
            total += t - s
            n += 1
    return total, n


def kernel_ns(events: Sequence[Event], lo: float, hi: float,
              kernel: str) -> Tuple[float, int]:
    """Device time and count of the calls of the kernel ``kernel``: the
    operations of that own name, or whose ``kernel_metadata`` names it;
    an operation that reads a call's output names it only as an operand
    and is not one."""
    return _ops_ns(events, lo, hi, lambda text: (
        op_name(text) == kernel
        or kernel_metadata(text).get("kernel") == kernel))


def custom_call_ns(events: Sequence[Event], lo: float, hi: float,
                   target: str) -> Tuple[float, int]:
    """Device time and count of the custom calls to ``target``."""
    return _ops_ns(events, lo, hi,
                   lambda text: target in _TARGET.findall(text))


def idle_gaps(events: Sequence[Event], host: Sequence[Event], lo: float,
              hi: float, top: int = 10) -> List[Tuple[str, float]]:
    """The longest stretches inside [lo, hi] with no device operation,
    each named by the shortest host event that covers its middle (what
    the host was doing), longest first."""
    busy = busy_intervals(events, lo, hi)
    gaps, cursor = [], lo
    for s, t in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, t)
    if hi > cursor:
        gaps.append((cursor, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, t in gaps[:top]:
        mid = (s + t) / 2
        cover = [e for e in host if e.start <= mid <= e.end]
        label = min(cover, key=lambda e: e.end - e.start).name \
            if cover else "(no host event)"
        out.append((label, (t - s) * 1e-9))
    return out


def device_events(trace: Trace, plane: Optional[str] = None) -> List[Event]:
    """The operations of one device plane (the first, by default)."""
    if not trace.ops:
        return []
    name = plane or sorted(trace.ops)[0]
    return trace.ops[name]
