"""The benchmark's own tests run on the CPU with the program's jnp
reference kernels; the repo's test suite (``tests/``) does not collect
them. Run them by path:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("REPRO_KERNEL_BACKEND", "ref")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
