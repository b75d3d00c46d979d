"""The grouped matmul of the dropless expert layer on the chip: the Pallas
kernel `moe_gmm` against `jax.lax.ragged_dot`, at a decode step's 128 pairs
and a 2048-token prefill's 8192, GLM-4.7-Flash's expert widths (64 experts,
2048 -> 1536 -> 2048). Prints milliseconds a call of the three matmuls of
one layer (gate, up, down) and the bytes' floor. Chip only:

    chiprun -- python tools/bench_gmm.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.ops.grouped_matmul import _gmm_call

E, D, F = 64, 2048, 1536


def layer(mm):
    def fn(x, gate, up, down, sizes):
        h = jax.nn.silu(mm(x, gate, sizes)) * mm(x, up, sizes)
        return mm(h, down, sizes)
    return jax.jit(fn)


def timed(fn, *args, n=20):
    fn(*args).block_until_ready()
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t) / n * 1e3


def main():
    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU")
    key = jax.random.PRNGKey(0)
    kg, ku, kd, kx = jax.random.split(key, 4)
    gate = (jax.random.normal(kg, (E, D, F)) * 0.02).astype(jnp.bfloat16)
    up = (jax.random.normal(ku, (E, D, F)) * 0.02).astype(jnp.bfloat16)
    down = (jax.random.normal(kd, (E, F, D)) * 0.02).astype(jnp.bfloat16)
    rng = np.random.default_rng(0)
    for pairs in (128, 8192):
        x = (jax.random.normal(kx, (pairs, D))).astype(jnp.bfloat16)
        experts = np.sort(rng.integers(0, E, pairs))
        sizes = jnp.asarray(np.bincount(experts, minlength=E), jnp.int32)
        touched = int((np.asarray(sizes) > 0).sum())
        floor = touched * 3 * D * F * 2 / 819e9 * 1e3
        pallas = layer(lambda a, b, s: _gmm_call(a, b, s, False))
        ragged = layer(lambda a, b, s: lax.ragged_dot(a, b, s))
        a = pallas(x, gate, up, down, sizes)
        b = ragged(x, gate, up, down, sizes)
        err = float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)
                            ).max())
        print(f"pairs {pairs}: experts touched {touched}, bytes' floor "
              f"{floor:.3f} ms; moe_gmm {timed(pallas, x, gate, up, down, sizes):.3f}"
              f" ms, ragged_dot {timed(ragged, x, gate, up, down, sizes):.3f}"
              f" ms; largest difference {err:.4g}", flush=True)


if __name__ == "__main__":
    main()
