"""store_read_s (store/loader): median over the window's cold starts of
the seconds the program spent reading the stage tensors out of the
store: the sum of ``read_ns`` over the ``loader.stage`` spans inside each
cycle's ``frontend.cold_start`` span."""

from bench.metrics import _spans as sp
from bench.metrics._common import median


def read(run):
    recs = sp.ring()
    if not recs or not run.cycles:
        return None
    kids = sp.children(recs)
    values = []
    for cyc in sp.by_cycle(recs, run.cycles):
        stages = [r for c in sp.closed(cyc, "frontend.cold_start")
                  for r in sp.subtree(c, kids) if r.name == "loader.stage"]
        if stages:
            values.append(sp.total(stages, ["read_ns"]) * 1e-9)
    return median(values)
