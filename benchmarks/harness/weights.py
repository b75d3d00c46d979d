"""Weights from `--seed`, made on the device in one jitted call, in the type
they are served or trained in. The tree has the layout the program's
`Transformer` holds (stacked layers), but nothing here comes from the
program: the reference and the system under test get the same arrays."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.harness.modelcfg import Sizes


def _shapes(s: Sizes) -> dict:
    L, e, f = s.layers, s.d_model, s.d_ff
    std = 0.02
    out_std = std / math.sqrt(2 * L)
    shapes = {
        "embed": ((s.vocab, e), std),
        "final_norm": ((e,), 0.1),
        "layers": {
            "attn_norm": ((L, e), 0.1),
            "wq": ((L, e, s.q_dim), std),
            "wk": ((L, e, s.kv_dim), std),
            "wv": ((L, e, s.kv_dim), std),
            "wo": ((L, s.q_dim, e), out_std),
            "mlp_norm": ((L, e), 0.1),
            "gate": ((L, e, f), std),
            "up": ((L, e, f), std),
            "down": ((L, f, e), out_std),
        },
    }
    if not s.tied:
        shapes["lm_head"] = ((e, s.vocab), std)
    return shapes


def _leaves(shapes):
    return jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
        and isinstance(x[0], tuple))


def make_weights(s: Sizes, seed: int, dtype=jnp.bfloat16):
    """Normal weights (0.02, output projections scaled down by depth, norm
    scales 0.1 around the identity) for every leaf, one program."""
    leaves, treedef = _leaves(_shapes(s))

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = [(jax.random.normal(k, shape, jnp.float32) * scale
                ).astype(dtype)
               for k, (shape, scale) in zip(keys, leaves)]
        return jax.tree_util.tree_unflatten(treedef, out)

    # fold the seed in two halves: seeds pass 2**31
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    return jax.jit(build)(key)


def param_count(s: Sizes) -> int:
    return sum(math.prod(shape) for shape, _ in _leaves(_shapes(s))[0])
