"""consolidate_s (consolidation): median over the window's cycles of the
host-clock seconds of ``ServerlessFrontend.consolidate``, ending when the
consolidated worker's parameters and pool are ready on the device."""

from bench.metrics._common import median


def read(run):
    return median([c.consolidate_s for c in run.cycles])
