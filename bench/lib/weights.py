"""Seeded random weights, made on the device.

A configuration's architecture module (``bench/references/<reference>.py``)
lists the leaves of the tree the program serves, in a fixed order, with
``layout(cfg)``. Over that list, ``make_params`` builds the whole tree in
one jitted call, in the served dtype, to hand to the system under test;
``layer_leaf`` rebuilds any single leaf of any layer for the reference,
bit for bit the same, so that the reference never holds more than one
layer and never reads what the system made.

Each leaf is uniform on [-sqrt(3) std, sqrt(3) std) (so its standard
deviation is ``std``), made from threefry bits with integer and exact
float arithmetic only: a vmapped and a single-layer call give the same
bits. A leaf's key is the seed's key folded with the leaf's index in the
layout and the index along its stack, so the order fixes every key.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

ROOT_TAG = 0x62656e63          # folded into the seed's key: "benc"


class Leaf(NamedTuple):
    path: Tuple[str, ...]       # key path in the served parameter tree
    shape: Tuple[int, ...]      # one layer's shape (without the stack axis)
    stack: int                  # length of the leading stack axis; 0: none
    std: float                  # 0 means a vector of ones (a norm's scale)


def padded_vocab(vocab: int) -> int:
    """Rows of the embedding and columns of the head as the served tree
    holds them: the vocabulary rounded up to a multiple of 256."""
    return -(-vocab // 256) * 256


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


def layer_leaf(key, leaves: Sequence[Leaf], index: int, layer: int, dtype):
    """Leaf ``index`` of the layout ``leaves`` at position ``layer`` of
    its stack (0 for a leaf that is not stacked), in ``dtype``."""
    leaf = leaves[index]
    return _uniform(_leaf_key(key, index, layer), leaf.shape, leaf.std,
                    dtype)


def make_params(leaves: Sequence[Leaf], seed: int, dtype) -> Dict:
    """The whole served tree of the layout ``leaves``, made on the default
    device in one jitted call."""

    def build(key):
        tree: Dict = {}
        for i, leaf in enumerate(leaves):
            if leaf.stack:
                val = jax.vmap(lambda l, i=i, leaf=leaf: _uniform(
                    _leaf_key(key, i, l), leaf.shape, leaf.std, dtype))(
                    jnp.arange(leaf.stack))
            else:
                val = _uniform(_leaf_key(key, i, 0), leaf.shape, leaf.std,
                               dtype)
            node = tree
            for k in leaf.path[:-1]:
                node = node.setdefault(k, {})
            node[leaf.path[-1]] = val
        return tree

    return jax.jit(build)(seed_key(seed))
