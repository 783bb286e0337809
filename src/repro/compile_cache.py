"""JAX's persistent compilation cache for the repo's entry points.

``enable_compile_cache()`` is called by ``chip_smoke.py`` and the
benchmark and example scripts, never at import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no path is
set here. Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
path, so that a second run of the same checkout finds what the first one
compiled.

A cache entry is found by a hash of the lowered program, and a Pallas
kernel's Mosaic body carries the source locations of its ops. By default
those are the whole Python call stack of absolute paths and line numbers,
so any edit that moves a caller's lines, or another checkout path, would
miss every executable that holds a kernel. The helper keeps only the
innermost frame (the kernel's own line) and writes paths relative to the
checkout.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(CHECKOUT) + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
