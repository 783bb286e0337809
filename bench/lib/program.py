"""The one place the benchmark maps its configuration files onto the
program under test (``src/repro``): a ``ModelConfig`` from a file's
Hugging Face keys, and the served tree's structure checked against the
benchmark's weight layout."""

from __future__ import annotations

import jax

from bench.lib import weights


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"])


def served_params(cfg: dict, seed: int):
    """The benchmark's seeded weights in the tree the program serves,
    after checking that the program expects exactly that tree."""
    from repro.models import build_model
    model = build_model(model_config(cfg))
    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)),
                        model.structs())
    params = weights.make_params(cfg, seed, cfg["torch_dtype"])
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
    if got != want:
        raise ValueError(f"weight layout does not match the program's "
                         f"tree:\n{got}\n!=\n{want}")
    return model, params
