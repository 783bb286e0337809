"""itl_p95_ms: 95th percentile of every gap between consecutive output
tokens of every request of the window, in milliseconds. A token's time
is when the step that emitted it returned to the caller."""

from bench.metrics._common import percentile


def read(run):
    gaps = [(b - a) * 1e3 for r in run.requests
            for a, b in zip(r["times"], r["times"][1:])]
    return percentile(gaps, 95)
