"""The gated delta rule's two kernels on the chip, at Olmo-Hybrid-7B's
widths (30 heads of 96 / 192): `gated_delta_chunk_fwd` over a bucket of
256, 1024 and 2048 positions (one linear layer's prefill recurrence) and
`gated_delta_step` at 32 lanes (one layer of a decode step), milliseconds a
call beside the bytes' floor, and each one's largest error against the
recurrence written position by position in float32. Chip only:

    chiprun -- python tools/bench_delta.py
    chiprun -- python tools/bench_delta.py --buckets 512,3072 --lanes 8
    chiprun -- python tools/bench_delta.py --tail

What `kernel.delta_chunk_roofline.answers3k` and
`kernel.delta_step_roofline.answers3k` read inside a cell, read alone
(PERF.md section 5). `--tail` times a decode step's convolution alone
instead, at the three recurrent cells' channels: `conv_tail_step` over the
pool as it is held since PR 51 (`tail_shape`: a slot whole tiles) against
the same gather, `conv_step` and scatter over the flat pool of before
(`(layers, slots + 1, (width - 1) x channels)`), microseconds a layer and
the share of 819 GB/s that the tails' bytes, read and written once, make
of it; each at the cells' own 33 slots and at 8 slots a lane.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import gated_delta as gd
from ray_tpu.ops import conv

H, DK, DV = 30, 96, 192
HBM = 819e9
# the recurrent cells' convolutions: (channels, layers, a bias)
TAILS = {"olmo-hybrid-7b": (11520, 9, False),
         "ling-3.0-flash": (12288, 6, False),
         "nemotron-3-super": (10240, 5, True)}
WIDTH = 4


def timed(fn, *args, n=20):
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def inputs(rows, seed=0):
    """q, k, v in bfloat16 as a layer hands them over, g, beta float32:
    decays of 0.7-0.98 a position, beta up to 2."""
    r = np.random.default_rng(seed)
    q = gd.l2_normalize(jnp.asarray(r.normal(size=(*rows, DK)))) / DK ** 0.5
    k = gd.l2_normalize(jnp.asarray(r.normal(size=(*rows, DK))))
    v = jnp.asarray(r.normal(size=(*rows, DV)), jnp.bfloat16)
    g = jnp.asarray(np.log(r.uniform(0.7, 0.98, size=rows)), jnp.float32)
    beta = jnp.asarray(r.uniform(0.1, 1.95, size=rows), jnp.float32)
    return q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v, g, beta


def flat_tail_step(x, w, pool, layer, slots, bias=None):
    """The step over the flat pool, as the three classes wrote it before
    PR 51: a slot's rows one run of `(width - 1) x channels` numbers."""
    B, n = x.shape[0], pool.shape[1] - 1
    tail = pool[layer, jnp.clip(slots, 0, n)].reshape(B, WIDTH - 1, -1)
    y, tail = conv.conv_step(x, tail, w, bias)
    return y, pool.at[layer, jnp.where(slots >= 0, slots, n + 1)].set(
        tail.reshape(B, -1), mode="drop")


def tails(B: int, rounds: int = 8, n: int = 20):
    """A step's convolutions alone: every layer of a pool, one after
    another as a step runs them (each layer's output the next one's
    input), `rounds` times a call so that the device and not the host's
    dispatch is timed. Two pools: the cells' own `B + 1` slots (alone in a
    program the compiler holds such a pool in fast memory whole) and 8
    slots a lane (170 MB at Olmo's sizes: in the chip's memory, as a
    cell's lies beside its weights)."""
    for name, (channels, layers, has_bias) in TAILS.items():
        r = np.random.default_rng(channels)
        x = jnp.asarray(r.normal(size=(B, channels)), jnp.bfloat16)
        w = jnp.asarray(r.normal(size=(WIDTH, channels)), jnp.bfloat16)
        bias = (jnp.asarray(r.normal(size=(channels,)), jnp.bfloat16)
                if has_bias else None)
        nbytes = (B * 2 * WIDTH * channels + WIDTH * channels) * 2
        fold = conv.tail_shape(WIDTH, channels)
        for slots in (B, 8 * B):
            lanes = jnp.asarray(r.permutation(slots)[:B], jnp.int32)
            line = (f"{name}, {channels} channels, {layers} layers, {B} "
                    f"lanes of {slots} slots:")
            for label, fn, slot in (
                    ("whole tiles", conv.conv_tail_step, fold),
                    ("flat", flat_tail_step, ((WIDTH - 1) * channels,))):
                def step(x, pool, fn=fn):
                    for li in range(rounds * layers):
                        x, pool = fn(x, w, pool, li % layers, lanes, bias)
                    return x, pool
                step = jax.jit(step, donate_argnums=(1,))
                pool = jnp.zeros((layers, slots + 1) + slot, jnp.bfloat16)
                _, pool = step(x, pool)
                jax.block_until_ready(pool)
                t = time.perf_counter()
                for _ in range(n):
                    _, pool = step(x, pool)
                jax.block_until_ready(pool)
                us = (time.perf_counter() - t) / (n * rounds * layers) * 1e6
                line += (f" {label} {us:.1f} us a layer "
                         f"({100 * nbytes / HBM / (us * 1e-6):.1f} % of 819 "
                         f"GB/s);")
            print(line, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--buckets", default="256,1024,2048")
    ap.add_argument("--lanes", type=int, default=32)
    ap.add_argument("--tail", action="store_true")
    a = ap.parse_args()
    print(f"device {jax.devices()[0].device_kind}; {H} heads of {DK} / {DV}")
    if a.tail:
        return tails(a.lanes)
    for s in (int(x) for x in a.buckets.split(",")):
        q, k, v, g, beta = inputs((H, s))
        fn = jax.jit(lambda *x: gd.gated_delta_prefill(*x, s))
        ms = timed(fn, q, k, v, g, beta)
        nbytes = s * H * (2 * DK + 2 * DV) * 2 + 2 * s * H * 4
        line = (f"chunk kernel, {s:5d} positions: {ms:7.3f} ms "
                f"(bytes' floor {nbytes / HBM * 1e3:.3f})")
        if s <= 1024:       # the scan is slow: one reading is enough
            want_o, want_s = jax.jit(gd.gated_delta_recurrence)(
                q, k, v, g, beta)
            o, state = fn(q, k, v, g, beta)
            line += (f"; error of o {float(jnp.abs(o - want_o).max()):.2e}"
                     f" of {float(jnp.abs(want_o).max()):.2e}, of the state"
                     f" {float(jnp.abs(state - want_s).max()):.2e}"
                     f" of {float(jnp.abs(want_s).max()):.2e}")
        print(line, flush=True)
    B = a.lanes
    q, k, v, g, beta = inputs((B, H), seed=1)
    pool = jnp.asarray(np.random.default_rng(2).normal(
        size=(2, B + 1, DK, H * DV)), jnp.float32)
    slots = jnp.arange(B, dtype=jnp.int32)
    want_o, want_pool = jax.jit(gd.gated_delta_step_reference)(
        q, k, v, g, beta, pool, 1, slots)
    step = jax.jit(lambda q, k, v, g, beta, pool: gd.gated_delta_step(
        q, k, v, g, beta, pool, 1, slots), donate_argnums=(5,))
    o, pool = step(q, k, v, g, beta, pool)
    err = (float(jnp.abs(o - want_o).max()),
           float(jnp.abs(pool - want_pool).max()))

    def many(q, k, v, g, beta, pool, n=20):     # the pool is donated on
        jax.block_until_ready(pool)
        t = time.perf_counter()
        for _ in range(n):
            o, pool = step(q, k, v, g, beta, pool)
        jax.block_until_ready(pool)
        return (time.perf_counter() - t) / n * 1e3

    ms = many(q, k, v, g, beta, pool)
    nbytes = B * (2 * DK * H * DV * 4 + H * (2 * DK + DV) * 2 + H * DV * 4)
    print(f"step kernel, {B} lanes: {ms:.3f} ms (bytes' floor "
          f"{nbytes / HBM * 1e3:.3f}); error of o {err[0]:.2e}, of the "
          f"state {err[1]:.2e}")


if __name__ == "__main__":
    main()
