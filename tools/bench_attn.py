"""Microbench: flash attention fwd/bwd on the real chip.

Compares the Pallas backward against the lax.scan backward at the
headline bench shape and sweeps block sizes. Not part of bench.py.
"""
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as A

B, H, S, D = 8, 16, 2048, 128


def timed(fn, *args, iters=20):
    jax.block_until_ready(fn(*args))  # compile
    jax.block_until_ready(fn(*args))  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.bfloat16)

    # causal attention FLOPs: fwd 2 matmuls, bwd 5 matmuls over s^2/2
    fwd_flops = 2 * 2 * B * H * S * S * D / 2
    bwd_flops = 5 * 2 * B * H * S * S * D / 2

    for bq, bk in [(512, 1024), (1024, 1024), (512, 2048), (1024, 2048),
                   (2048, 1024), (2048, 2048), (256, 1024), (256, 2048)]:
        try:
            f = jax.jit(functools.partial(
                A.flash_attention, causal=True, block_q=bq, block_k=bk))
            tf = timed(f, q, k, v)

            g = jax.jit(jax.grad(
                lambda q_, k_, v_: jnp.sum(
                    A.flash_attention(q_, k_, v_, causal=True,
                                      block_q=bq, block_k=bk)
                    .astype(jnp.float32)),
                argnums=(0, 1, 2)))
            tg = timed(g, q, k, v)
            tb = tg - tf
            print(f"bq={bq:5d} bk={bk:5d} fwd {tf*1e3:7.2f}ms "
                  f"({fwd_flops/tf/1e12:5.1f}TF/s) fwd+bwd {tg*1e3:7.2f}ms "
                  f"bwd-only {tb*1e3:7.2f}ms ({bwd_flops/tb/1e12:5.1f}TF/s)")
        except Exception as e:
            print(f"bq={bq} bk={bk} FAILED: {type(e).__name__}: "
                  f"{str(e)[:120]}")

    # old scan backward for reference
    @functools.partial(jax.custom_vjp, nondiff_argnums=())
    def scan_flash(q, k, v):
        return A._flash_fwd(q, k, v, True, D ** -0.5, 128, 128, False)[0]

    def scan_fwd(q, k, v):
        o, lse = A._flash_fwd(q, k, v, True, D ** -0.5, 128, 128, False)
        return o, (q, k, v, o, lse)

    def scan_bwd(res, do):
        q, k, v, o, lse = res
        return A._flash_bwd_xla(q, k, v, o, lse, do, True, D ** -0.5, 128)

    scan_flash.defvjp(scan_fwd, scan_bwd)
    g = jax.jit(jax.grad(lambda q_, k_, v_: jnp.sum(
        scan_flash(q_, k_, v_).astype(jnp.float32)), argnums=(0, 1, 2)))
    tf = timed(jax.jit(functools.partial(
        A.flash_attention, causal=True, block_q=128, block_k=128)), q, k, v)
    tg = timed(g, q, k, v)
    tb = tg - tf
    print(f"lax.scan bwd          fwd+bwd {tg*1e3:7.2f}ms "
          f"bwd-only {tb*1e3:7.2f}ms ({bwd_flops/tb/1e12:5.1f}TF/s)")


if __name__ == "__main__":
    main()
