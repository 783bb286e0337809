"""The benchmark's own tests run on the CPU with the program's jnp
reference kernels; the repo's test suite (``tests/``) does not collect
them. Run them by path:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("REPRO_KERNEL_BACKEND", "ref")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def traced_lengths():
    """Context lengths of a coldburst cycle's traced decode steps (steps
    20-51: each request's prompt plus the steps it has decoded)."""
    from bench.lib import traffic
    mix = json.loads((ROOT / "bench" / "traffic" / "coldburst.json")
                     .read_text())
    prompts = traffic.lengths(mix["prompt_len"], mix["burst"])
    outputs = traffic.lengths(mix["output_len"], mix["burst"])
    return [[p + n + 1 for p, o in zip(prompts, outputs) if o > n]
            for n in range(20, 52)]
