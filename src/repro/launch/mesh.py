"""Production meshes. Functions (not module-level constants) so importing
never touches jax device state.

Every axis is ``Auto``: the models annotate activations with
``with_sharding_constraint`` (distributed/sharding.py ``constrain``),
which only accepts Auto axes, and ``jax.make_mesh`` defaults to Explicit
ones."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_pp_mesh(n_stages: int = 4):
    """Technique-representative mesh: a pipeline axis for the paper's
    cold-start groups, within one pod."""
    return _mesh((n_stages, 256 // n_stages // 16, 16),
                 ("stage", "data", "model"))


def make_cpu_mesh():
    """Single-device mesh for tests/examples."""
    return _mesh((1, 1), ("data", "model"))
