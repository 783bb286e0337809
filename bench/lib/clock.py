"""Host-side clocks: JAX's own account of building executables, and the
live device bytes."""

from __future__ import annotations

import jax


class CompileClock:
    """Seconds JAX reports through ``jax.monitoring`` for tracing,
    lowering, and compiling or loading from the persistent cache, and that
    cache's lookups, hits and misses."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
              "/jax/core/compile/backend_compile_duration": "compile_s",
              "/jax/compilation_cache/compile_requests_use_cache":
                  "cache_lookups",
              "/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        self.totals = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._add)
        jax.monitoring.register_event_listener(
            lambda event, **kw: self._add(event, 1))

    def _add(self, event, value, **kw):
        if event in self.EVENTS:
            self.totals[self.EVENTS[event]] += value

    def snapshot(self) -> dict:
        return dict(self.totals)

    def since(self, before: dict) -> dict:
        return {k: v - before[k] for k, v in self.totals.items()}


def build_seconds(delta: dict) -> float:
    return delta["trace_s"] + delta["lower_s"] + delta["compile_s"]


def live_device_bytes() -> int:
    return sum(a.nbytes for a in jax.live_arrays())


def ready(tree) -> None:
    jax.block_until_ready(jax.tree.leaves(tree))
