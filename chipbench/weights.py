"""Random weights from the seed, made on the device in one jitted call and in
the type they are held in. The tree is the family's (``family.leaves``): its
layout is the one the program's entry points take, and each leaf says how
it starts. The reference takes the same tree, made again from the same
seed."""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

STD = 0.02


class Leaf(NamedTuple):
    """One leaf of a family's tree: its shape and how it starts: ``normal``
    (x 0.02: a matrix), ``one`` (a norm's scale) or ``zero`` (a bias)."""
    shape: Tuple[int, ...]
    start: str = "normal"


def is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def fold_seed(seed: int) -> int:
    """Any whole number to what a 32-bit PRNG seed holds."""
    return int(seed) % (2 ** 31 - 1)


def _start(key, leaf: Leaf, dtype):
    if leaf.start == "normal":
        return jax.random.normal(key, leaf.shape, dtype) \
            * jnp.asarray(STD, dtype)
    if leaf.start == "one":
        return jnp.ones(leaf.shape, dtype)
    if leaf.start == "zero":
        return jnp.zeros(leaf.shape, dtype)
    raise ValueError(f"a leaf starts normal, one or zero, not {leaf.start!r}")


def _make(key, leaves, dtype):
    """Every leaf gets a key of its own, in the tree's flattened order."""
    flat, tree = jax.tree_util.tree_flatten(leaves, is_leaf=is_leaf)
    keys = jax.random.split(key, len(flat))
    return jax.tree_util.tree_unflatten(
        tree, [_start(k, leaf, dtype) for k, leaf in zip(keys, flat)])


def make_weights(leaves, seed: int, dtype=jnp.bfloat16, shardings=None):
    """The whole tree in one jitted call. ``leaves`` is the family's tree of
    ``Leaf``; ``shardings`` (a tree of shardings, as the program laid its
    own parameters out) places it."""
    fn = jax.jit(functools.partial(_make, leaves=leaves, dtype=dtype),
                 out_shardings=shardings)
    return fn(jax.random.PRNGKey(fold_seed(seed)))
