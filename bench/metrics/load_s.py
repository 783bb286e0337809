"""load_s (store/loader): median over the window's cold starts of the
host-clock seconds of ``ServerlessFrontend.cold_start``, ending when every
worker's parameters and page pools are ready on the device."""

from bench.metrics._common import median


def read(run):
    return median([c.load_s for c in run.cycles])
