"""cold_ttft_s: median, over every request of the window (each arrived at
a model scaled to zero), of the seconds from its arrival, taken before
``cold_start``, to its first token."""

from bench.metrics._common import median


def read(run):
    return median([r["times"][0] - r["arrival"] for r in run.requests
                   if r["times"]])
