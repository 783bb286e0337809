"""consolidate_fetch_s (consolidation): median over the window's cycles of
the seconds of the ``frontend.full_fetch`` span, the read of the full
weights out of the store that consolidation starts with."""

from bench.metrics import _spans as sp
from bench.metrics._common import median


def read(run):
    recs = sp.ring()
    if not recs or not run.cycles:
        return None
    values = []
    for cyc in sp.by_cycle(recs, run.cycles):
        fetches = sp.closed(cyc, "frontend.full_fetch")
        if fetches:
            values.append(sum(r.end_ns - r.start_ns for r in fetches) * 1e-9)
    return median(values)
