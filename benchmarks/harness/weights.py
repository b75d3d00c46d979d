"""Weights from `--seed`, made on the device in one jitted call, in the type
they are served or trained in. The tree of `(shape, std)` is the model
module's (`weight_shapes`), with the layout the program holds, but nothing
here comes from the program: the reference and the system under test get
the same arrays."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def leaves(shapes):
    """(the `(shape, std)` leaves in flattening order, the tree's def)."""
    return jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
        and isinstance(x[0], tuple))


def make_weights(shapes, seed: int, dtype=jnp.bfloat16):
    """Normal weights of each leaf's own std for every leaf of the tree,
    one program. The key is split over the leaves in flattening order, so a
    seed gives a tree the same arrays wherever it is built."""
    flat, treedef = leaves(shapes)

    def build(key):
        keys = jax.random.split(key, len(flat))
        out = [(jax.random.normal(k, shape, jnp.float32) * scale
                ).astype(dtype)
               for k, (shape, scale) in zip(keys, flat)]
        return jax.tree_util.tree_unflatten(treedef, out)

    # fold the seed in two halves: seeds pass 2**31
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    return jax.jit(build)(key)


def param_count(shapes) -> int:
    return sum(math.prod(shape) for shape, _ in leaves(shapes)[0])
