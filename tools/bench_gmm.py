"""The grouped matmul of the dropless expert layer on the chip: the Pallas
kernel `moe_gmm` against `jax.lax.ragged_dot`, at the pairs a decode step
and a prefill bucket of the two expert cells hand it: GLM-4.7-Flash's
widths (64 experts, 2048 -> 1536 -> 2048; 128, 4096 and 8192 pairs) and
Laguna-XS.2's (256 experts, 2048 -> 512 -> 2048; 256, 32,768 and 65,536).
Prints the tiles the kernel chose (gate and up, then down), milliseconds a
call of the three matmuls of one layer and the bytes' floor. Chip only:

    chiprun -- python tools/bench_gmm.py
    chiprun -- python tools/bench_gmm.py --sweep    # every tile, no ragged_dot
    chiprun -- python tools/bench_gmm.py --pairs 2048,65536    # other buckets

`--sweep` puts each row tile from 16 to 512 and each column block (the
whole n, 512) in the place of `gmm_tile_shape`'s answer: what the rule in
`ops/grouped_matmul.py` was chosen from (PERF.md section 6, PR 36).
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.ops import grouped_matmul as gm

D = 2048
# name: (experts, expert width, pairs of a decode step and of prefill buckets)
WIDTHS = {"glm-4.7-flash": (64, 1536, (128, 4096, 8192)),
          "laguna-xs.2": (256, 512, (256, 32768, 65536))}


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


def pallas_layer():
    jax.clear_caches()      # `_gmm_call` keeps the tiles it was traced with
    return layer(lambda a, b, s: gm._gmm_call(a, b, s, False))


def sweep(args, pairs):
    """Each row tile and column block in the place of the rule's."""
    rule = gm.gmm_tile_shape
    for tm in (16, 32, 64, 128, 256, 512):
        for tn in ("n", 512):
            if tm > pairs:
                continue
            gm.gmm_tile_shape = lambda m, k, n, dt: (
                tm, n if tn == "n" else tn)
            try:
                took = f"{timed(pallas_layer(), *args):.3f} ms"
            except Exception as e:      # say so and go on
                took = f"failed: {str(e)[:200]}"
            print(f"  tm {tm} tn {tn}: moe_gmm {took}", flush=True)
    gm.gmm_tile_shape = rule


def compare(args, pairs, F):
    """The kernel at the rule's tiles against `ragged_dot`."""
    # a tree from before PR 36 has two constants and no rule
    rule = getattr(gm, "gmm_tile_shape", None)
    tiles = "TILE_M, TILE_N as they stand" if rule is None else (
        f"{rule(pairs, D, F, jnp.bfloat16)} and "
        f"{rule(pairs, F, D, jnp.bfloat16)}")
    pallas = pallas_layer()
    ragged = layer(lambda a, b, s: lax.ragged_dot(a, b, s))
    err = float(jnp.abs(pallas(*args).astype(jnp.float32)
                        - ragged(*args).astype(jnp.float32)).max())
    print(f"  tiles {tiles}; moe_gmm {timed(pallas, *args):.3f} ms, "
          f"ragged_dot {timed(ragged, *args):.3f} ms; largest difference "
          f"{err:.4g}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--pairs", help="comma-separated, in place of each "
                    "model's own")
    opts = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU")
    rng = np.random.default_rng(0)
    for name, (E, F, all_pairs) in WIDTHS.items():
        kg, ku, kd, kx = jax.random.split(jax.random.PRNGKey(0), 4)
        gate = (jax.random.normal(kg, (E, D, F)) * 0.02).astype(jnp.bfloat16)
        up = (jax.random.normal(ku, (E, D, F)) * 0.02).astype(jnp.bfloat16)
        down = (jax.random.normal(kd, (E, F, D)) * 0.02).astype(jnp.bfloat16)
        if opts.pairs:
            all_pairs = [int(p) for p in opts.pairs.split(",")]
        for pairs in all_pairs:
            x = (jax.random.normal(kx, (pairs, D))).astype(jnp.bfloat16)
            experts = np.sort(rng.integers(0, E, pairs))
            sizes = jnp.asarray(np.bincount(experts, minlength=E), jnp.int32)
            touched = int((np.asarray(sizes) > 0).sum())
            floor = touched * 3 * D * F * 2 / 819e9 * 1e3
            print(f"{name} pairs {pairs}: experts touched {touched}, "
                  f"bytes' floor {floor:.3f} ms", flush=True)
            args = (x, gate, up, down, sizes)
            if opts.sweep:
                sweep(args, pairs)
            else:
                compare(args, pairs, F)


if __name__ == "__main__":
    main()
