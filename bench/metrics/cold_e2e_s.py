"""cold_e2e_s: median, over every request of the window, of the seconds
from its arrival to its last token: the two-stage pipeline's decode and
the consolidation stall included."""

from bench.metrics._common import median


def read(run):
    return median([r["times"][-1] - r["arrival"] for r in run.requests
                   if r["times"]])
