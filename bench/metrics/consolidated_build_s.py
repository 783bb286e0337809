"""consolidated_build_s (consolidation): median over the window's cycles
of the seconds JAX spent tracing, lowering and compiling (or loading from
the persistent cache) inside the ``engine.step`` spans that start after
the cycle's ``frontend.consolidate`` span ended: the consolidated
worker's executables, built again at every consolidation."""

from bench.metrics import _spans as sp
from bench.metrics._common import median


def read(run):
    recs = sp.ring()
    if not recs or not run.cycles:
        return None
    kids = sp.children(recs)
    values = []
    for cyc in sp.by_cycle(recs, run.cycles):
        done = sp.closed(cyc, "frontend.consolidate")
        if not done:
            continue
        after = [r for r in sp.closed(cyc, "engine.step")
                 if r.start_ns >= done[0].end_ns]
        values.append(sp.total([d for r in after
                                for d in sp.subtree(r, kids)],
                               sp.BUILD_NS) * 1e-9)
    return median(values)
