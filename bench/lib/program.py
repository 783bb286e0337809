"""The one place the benchmark maps its configuration files onto the
program under test (``src/repro``). What a configuration's keys mean is
its architecture module's to say (``check.reference_module``): the
program's ``ModelConfig`` and the served tree's layout come from there,
and the tree is checked against the program's own before it is served."""

from __future__ import annotations

import jax

from bench.lib import check, weights


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    return check.reference_module(cfg).model_config(cfg)


def served_params(cfg: dict, seed: int):
    """The benchmark's seeded weights in the tree the program serves,
    after checking that the program expects exactly that tree."""
    from repro.models import build_model
    arch = check.reference_module(cfg)
    model = build_model(arch.model_config(cfg))
    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)),
                        model.structs())
    params = weights.make_params(arch.layout(cfg), seed, cfg["torch_dtype"])
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
    if got != want:
        raise ValueError(f"weight layout does not match the program's "
                         f"tree:\n{got}\n!=\n{want}")
    return model, params
