"""The least time the chip needs for a given work, from its operations
and bytes. What a step's work is (its operations, its bytes, how many
kernel calls it makes) is the configuration's architecture module's to
count (``bench/references/<reference>.py``), from shapes alone."""

from __future__ import annotations


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """Least time for the work on the chip, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
