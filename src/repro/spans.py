"""Spans and counters of the serving path: the program's one tracing system.

``span(name, **attrs)`` marks a stretch of host work in two places at once:

* a ``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation`` where the
  attrs hold ``step_num``), which puts the span on the device trace's clock
  while the profiler runs and records nothing while it does not;
* a ``Record`` appended to a bounded ring in memory, always: its name,
  ``start_ns`` and ``end_ns`` on ``time.perf_counter_ns()``, the index of
  the span it opened inside (``parent``), its attrs and its counters.

``count(key, n)`` adds to the counters of the innermost span open on the
calling thread. A ``jax.monitoring`` listener registered at import counts
what JAX builds into the same span: ``trace_ns``, ``lower_ns`` and
``compile_ns`` (a backend compile or a load from the persistent cache),
``executables`` (one per top-level executable built), ``cache_hits`` and
``cache_misses``. Counts made with no span open go to ``unattributed``.

``records()`` is the one reader. The ring keeps the newest ``RING``
records. Nothing here waits for the device or touches its arrays.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Deque, Dict, List, Optional

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = ["RING", "Record", "count", "records", "span", "unattributed"]

RING = 1 << 15

_BUILD_SECONDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_ns",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_ns",
    "/jax/core/compile/backend_compile_duration": "compile_ns",
}
_BUILD_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class Record:
    """One span. ``end_ns`` is None while the span is open; ``parent`` is
    the ``index`` of the span it opened inside, or None."""

    __slots__ = ("index", "name", "parent", "attrs", "start_ns", "end_ns",
                 "counters")

    def __init__(self, index: int, name: str, parent: Optional[int],
                 attrs: dict):
        self.index = index
        self.name = name
        self.parent = parent
        self.attrs = attrs
        self.start_ns = 0
        self.end_ns: Optional[int] = None
        self.counters: Dict[str, float] = {}

    def __repr__(self) -> str:
        return (f"Record({self.index}, {self.name!r}, parent={self.parent}, "
                f"attrs={self.attrs}, counters={self.counters}, "
                f"ns={self.start_ns}..{self.end_ns})")


_ring: Deque[Record] = collections.deque(maxlen=RING)
_index = itertools.count()
_local = threading.local()
unattributed: Dict[str, float] = {}


def _stack() -> List[Record]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """Context manager over one span; ``with span(...) as rec`` gives the
    ring's ``Record``, whose attrs the block may complete."""

    __slots__ = ("_name", "_attrs", "_record", "_annotation")

    def __init__(self, name: str, **attrs):
        self._name = name
        self._attrs = attrs

    def __enter__(self) -> Record:
        stack = _stack()
        rec = Record(next(_index), self._name,
                     stack[-1].index if stack else None, self._attrs)
        ann = (StepTraceAnnotation if "step_num" in self._attrs
               else TraceAnnotation)(self._name, **self._attrs)
        ann.__enter__()
        self._annotation = ann
        self._record = rec
        stack.append(rec)
        _ring.append(rec)
        rec.start_ns = time.perf_counter_ns()
        return rec

    def __exit__(self, *exc) -> None:
        self._record.end_ns = time.perf_counter_ns()
        _stack().pop()
        self._annotation.__exit__(*exc)


def count(key: str, n: float = 1) -> None:
    """Add ``n`` to ``key`` on the innermost open span of this thread."""
    stack = getattr(_local, "stack", None)
    counters = stack[-1].counters if stack else unattributed
    counters[key] = counters.get(key, 0) + n


def records() -> List[Record]:
    """The ring, oldest first (open spans included, ``end_ns`` None)."""
    return list(_ring)


def _on_duration(event: str, seconds: float, **_) -> None:
    key = _BUILD_SECONDS.get(event)
    if key is None:
        return
    count(key, int(seconds * 1e9))
    if key == "compile_ns":
        count("executables")


def _on_event(event: str, **_) -> None:
    key = _BUILD_EVENTS.get(event)
    if key is not None:
        count(key)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
