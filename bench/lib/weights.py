"""Seeded random weights for a dense GQA decoder, made on the device.

One layout serves both sides of the comparison: ``make_params`` builds
the whole tree in one jitted call, in the served dtype, to hand to the
system under test; ``layer_leaf`` rebuilds any single leaf of any layer
for the reference, bit for bit the same, so that the reference never
holds more than one layer and never reads what the system made.

Each leaf is uniform on [-sqrt(3) std, sqrt(3) std) (so its standard
deviation is ``std``), made from threefry bits with integer and exact
float arithmetic only: a vmapped and a single-layer call give the same
bits.
"""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp

ROOT_TAG = 0x62656e63          # folded into the seed's key: "benc"


class Leaf(NamedTuple):
    path: Tuple[str, ...]       # key path in the served parameter tree
    shape: Tuple[int, ...]      # one layer's shape (without the layer axis)
    stacked: bool               # one per layer, stacked on a leading axis
    std: float                  # 0 means a vector of ones (a norm's scale)


def padded_vocab(vocab: int) -> int:
    """Rows of the embedding and columns of the head as the served tree
    holds them: the vocabulary rounded up to a multiple of 256."""
    return -(-vocab // 256) * 256


def layout(cfg: dict) -> List[Leaf]:
    """Every leaf of the served tree of a dense GQA decoder with SwiGLU
    and RMSNorm, in a fixed order (the order fixes each leaf's key)."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    vp = padded_vocab(cfg["vocab_size"])
    blk = ("blocks", "slot00")
    return [
        Leaf(("embed", "tok"), (vp, d), False, 1.0),
        Leaf(blk + ("mixer", "w_q"), (d, hq * hd), True, d ** -0.5),
        Leaf(blk + ("mixer", "w_k"), (d, hkv * hd), True, d ** -0.5),
        Leaf(blk + ("mixer", "w_v"), (d, hkv * hd), True, d ** -0.5),
        Leaf(blk + ("mixer", "w_o"), (hq * hd, d), True, (hq * hd) ** -0.5),
        Leaf(blk + ("mixer", "norm"), (d,), True, 0.0),
        Leaf(blk + ("mlp", "w_gate"), (d, ff), True, d ** -0.5),
        Leaf(blk + ("mlp", "w_up"), (d, ff), True, d ** -0.5),
        Leaf(blk + ("mlp", "w_down"), (ff, d), True, ff ** -0.5),
        Leaf(blk + ("mlp", "norm"), (d,), True, 0.0),
        Leaf(("final_norm",), (d,), False, 0.0),
        Leaf(("lm_head",), (d, vp), False, d ** -0.5),
    ]


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63: the low and the high
    32 bits are folded in apart."""
    key = jax.random.fold_in(jax.random.PRNGKey(ROOT_TAG),
                             seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _uniform(key, shape, std: float, dtype):
    if std == 0.0:
        return jnp.ones(shape, dtype)
    bits = jax.random.bits(key, shape, jnp.uint32)
    # 23 random mantissa bits -> [1, 2) exactly, then an exact affine map
    one_two = jax.lax.bitcast_convert_type(
        (bits >> 9) | jnp.uint32(0x3F800000), jnp.float32)
    half = jnp.float32(std * math.sqrt(3.0))
    return ((one_two - 1.5) * (2 * half)).astype(dtype)


def _leaf_key(key, index: int, layer: int):
    return jax.random.fold_in(jax.random.fold_in(key, index), layer)


def layer_leaf(key, cfg: dict, index: int, layer: int, dtype):
    """Leaf ``index`` of ``layout(cfg)`` for one layer (0 for leaves that
    are not stacked), in ``dtype``."""
    leaf = layout(cfg)[index]
    return _uniform(_leaf_key(key, index, layer), leaf.shape, leaf.std,
                    dtype)


def make_params(cfg: dict, seed: int, dtype) -> Dict:
    """The whole served tree, made on the default device in one jitted
    call."""
    n_layers = cfg["num_hidden_layers"]
    leaves = layout(cfg)

    def build(key):
        tree: Dict = {}
        for i, leaf in enumerate(leaves):
            if leaf.stacked:
                val = jax.vmap(lambda l, i=i, leaf=leaf: _uniform(
                    _leaf_key(key, i, l), leaf.shape, leaf.std, dtype))(
                    jnp.arange(n_layers))
            else:
                val = _uniform(_leaf_key(key, i, 0), leaf.shape, leaf.std,
                               dtype)
            node = tree
            for k in leaf.path[:-1]:
                node = node.setdefault(k, {})
            node[leaf.path[-1]] = val
        return tree

    return jax.jit(build)(seed_key(seed))
