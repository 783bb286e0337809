"""first_step_builds (compile): median over the window's cold starts of
the executables JAX built (compiled, or loaded from the persistent
cache) inside each cycle's first ``engine.step`` span, the spans opened
inside it included."""

from bench.metrics import _spans as sp
from bench.metrics._common import median


def read(run):
    recs = sp.ring()
    if not recs or not run.cycles:
        return None
    kids = sp.children(recs)
    values = []
    for cyc in sp.by_cycle(recs, run.cycles):
        steps = sp.closed(cyc, "engine.step")
        if steps:
            values.append(sp.total(sp.subtree(steps[0], kids),
                                   ["executables"]))
    return median(values)
