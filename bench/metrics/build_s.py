"""build_s (compile): median over the window's cold starts of the seconds
JAX reports (``jax.monitoring``) tracing, lowering and compiling or
loading executables inside each cold start's first step."""

from bench.metrics._common import median


def read(run):
    return median([c.build_s for c in run.cycles])
