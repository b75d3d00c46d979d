"""The flash forward on the chip, alone: `ops.attention.flash_attention`,
causal, batch 1, bfloat16, over (heads, key width, value width, bucket,
block_q, block_k), 8 calls (layers) a program, each one's queries hanging
on the one before. Prints milliseconds a call, microseconds a grid step
and the share of the chip's 197 TFLOP/s that the causal half's operations
(QK^T at the keys' width and PV at the values', `s^2 / 2` pairs a head)
make of it; a pair of blocks the compiler refuses (more fast memory than a
kernel may use) is said so and left out. Chip only:

    chiprun -- python tools/bench_flash.py --latent
    chiprun -- python tools/bench_flash.py --heads 48 --d 128 --dv 128 \\
        --buckets 1024,8192 --blocks 128x128,512x512,1024x1024

`--latent` runs the shapes at which the three latent-attention classes
call the kernel in a prefill (`models/latent.py`: GLM-4.7-Flash 20 heads
of 256 / 256, LongCat-Flash 64 of 192 / 128, Ling-3.0-flash 32 of 192 /
128) over the candidate blocks and buckets 256 to 16,384, and then prints
the table `models.latent.PREFILL_BLOCKS` was chosen from beside it: a
bucket and widths, the fastest pair, or the smaller program where two are
within 3 % (PERF.md section 6, PR 53).

`--kv-heads` gives the keys and values fewer heads than the queries
(grouped-query attention; default: the heads). `--dense` is the shape at
which the dense class's prefill calls the kernel (`models/decode.py`:
InternLM2-1.8B, 16 query heads over 8 kv heads of 128) over buckets 256 to
4096, the deployment's context limit, with the same table against
`models.gqa.FULL_BLOCKS` (PERF.md section 6, PR 57), 3 min of one chip.

`--backward` runs the backward kernel alone (`ops.attention.
_flash_bwd_pallas`, 5 calls a program, each one's `do` hanging on the `dq`
before) at the training cell's shapes (2 x 32 heads over 8 kv heads of 128,
4096 tokens) and at 8192 and 16,384 tokens in one sequence, over blocks of
512 and 1024 and the diagonal's sub-blocks 256, 512 and the whole block:
milliseconds a call and the share of the peak that the causal half's five
matmuls make of it. `--parent DIR` (a `git archive` of another commit) runs
that tree's backward at the same shapes as the first rows; `DIAG_BLOCK` was
chosen from this table (PERF.md section 6, PR 55):

    chiprun -- python tools/bench_flash.py --backward --parent .bench_base/parent
"""
import argparse
import importlib.util
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks.harness.peaks import PEAKS
from ray_tpu.models.gqa import FULL_BLOCKS
from ray_tpu.models.latent import PREFILL_BLOCKS
from ray_tpu.ops import attention
from ray_tpu.ops.attention import flash_attention

LAYERS, PEAK = 8, PEAKS["TPU v5 lite"]["bf16_flops"]
# the latent classes' prefills: (heads, kv heads, key width, value width)
LATENT = {"glm-4.7-flash": (20, 20, 256, 256),
          "longcat-flash": (64, 64, 192, 128),
          "ling-3.0-flash": (32, 32, 192, 128)}
# the dense class's, and the buckets up to its deployment's context limit
DENSE = {"internlm2-1.8b": (16, 8, 128, 128)}
DENSE_BUCKETS = (256, 512, 1024, 2048, 4096)
BUCKETS = (256, 512, 1024, 2048, 4096, 8192, 16384)
BLOCKS = ((128, 128), (256, 256), (256, 512), (512, 512), (512, 1024),
          (1024, 512), (1024, 1024), (512, 2048), (1024, 2048))
WITHIN = 0.03       # two pairs this close: the smaller program
# the backward: the training cell's heads, calls a program, lengths, (block,
# the diagonal's sub-block)
BWD_HEADS, BWD_KV_HEADS, BWD_D, BWD_CALLS, BWD_TOKENS = 32, 8, 128, 5, 8192
BWD_LENGTHS = (4096, 8192, 16384)
BWD_BLOCKS = ((512, 256), (512, 512), (1024, 256), (1024, 512), (1024, 1024))


def program(block_q, block_k):
    """`LAYERS` calls, the first rows of each one's queries moved by the
    output before (an update in place: nothing beside the kernel that a
    bucket's size would show in)."""
    def run(q, k, v):
        for _ in range(LAYERS):
            out = flash_attention(q, k, v, causal=True, block_q=block_q,
                                  block_k=block_k)
            q = q.at[:, :, :8, :v.shape[-1]].add(out[:, :, :8] * 1e-3)
        return q
    return jax.jit(run)


def timed(fn, *args, calls=LAYERS):
    """Milliseconds a call: the least of three batches of programs, each
    long enough (0.05 s or five programs) to be read on the host's
    clock."""
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    jax.block_until_ready(fn(*args))
    n = max(5, int(0.05 / max(time.perf_counter() - t, 1e-5)))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / n)
    return best * 1e3 / calls


def shape_name(heads, kv_heads, d, dv):
    return (f"{heads} heads" + (f" over {kv_heads}" if kv_heads != heads
                                else "") + f" of {d} / {dv}")


def sweep(heads, kv_heads, d, dv, buckets, blocks):
    """{bucket: {(block_q, block_k): ms a call}} of one shape, a line a
    reading; blocks larger than the bucket are the bucket's own (as the
    call cuts them) and read once."""
    key = jax.random.PRNGKey(0)
    what = shape_name(heads, kv_heads, d, dv)
    out = {}
    for s in buckets:
        q, k = (jax.random.normal(kk, (1, n, s, d), jnp.bfloat16)
                for kk, n in zip(jax.random.split(key), (heads, kv_heads)))
        v = k[..., :dv]
        flops = 2.0 * (s * s / 2.0) * (d + dv) * heads
        out[s] = {}
        for bq, bk in blocks:
            cut = (min(bq, s), min(bk, s))
            if cut in out[s]:
                continue
            steps = heads * -(-s // cut[0]) * -(-s // cut[1])
            try:
                ms = timed(program(*cut), q, k, v)
            except Exception as e:      # say so and go on
                print(f"{what}, bucket {s}, blocks "
                      f"{cut[0]} x {cut[1]}: does not compile: "
                      f"{' '.join(str(e).split())[:200]}", flush=True)
                continue
            out[s][cut] = ms
            print(f"{what}, bucket {s:5d}, blocks "
                  f"{cut[0]:4d} x {cut[1]:4d}: {ms:8.4f} ms a call, "
                  f"{ms * 1e3 / steps:6.3f} us a grid step of {steps:6d}, "
                  f"{100 * flops / PEAK / (ms * 1e-3):5.1f} % of the peak",
                  flush=True)
    return out


def backward_program(bwd, block, **diag):
    """`BWD_CALLS` backwards of one forward's residuals, the first rows of
    each one's `do` moved by the `dq` before."""
    def run(q, k, v, o, lse, do):
        for _ in range(BWD_CALLS):
            dq, dk, dv = bwd(q, k, v, o, lse, do, True, BWD_D ** -0.5, block,
                             block, False, **diag)
            do = do.at[:, :, :8].add(
                (dq[:, :, :8] + dk[:, :1, :8] + dv[:, :1, :8]) * 1e-3)
        return do
    return jax.jit(run)


def sweep_backward(lengths, parent):
    """A line a reading: the parent tree's backward at each block (where
    `parent` names a tree), then this tree's over `BWD_BLOCKS`."""
    rows = [("this tree", attention._flash_bwd_pallas, block, {
        "diag_block": diag}) for block, diag in BWD_BLOCKS]
    if parent:
        spec = importlib.util.spec_from_file_location(
            "parent_attention",
            os.path.join(parent, "ray_tpu", "ops", "attention.py"))
        theirs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(theirs)
        rows = [("parent", theirs._flash_bwd_pallas, block, {})
                for block in sorted({b for b, _ in BWD_BLOCKS})] + rows
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    for s in lengths:
        b = max(1, BWD_TOKENS // s)
        q, do = (jax.random.normal(kk, (b, BWD_HEADS, s, BWD_D),
                                   jnp.bfloat16) for kk in keys[:2])
        k, v = (jax.random.normal(kk, (b, BWD_KV_HEADS, s, BWD_D),
                                  jnp.bfloat16) for kk in keys[2:])
        o, lse = flash_attention(q, k, v, causal=True, block_q=1024,
                                 block_k=1024, return_lse=True)
        flops = 5 * 2.0 * (s * s / 2.0) * BWD_D * BWD_HEADS * b
        for whose, bwd, block, diag in rows:
            what = (f"{whose}, {b} x {BWD_HEADS} heads over {BWD_KV_HEADS} "
                    f"of {BWD_D}, {s:5d} tokens, blocks {block:4d}"
                    + "".join(f", diagonal {d:4d}" for d in diag.values()))
            try:
                ms = timed(backward_program(bwd, block, **diag), q, k, v, o,
                           lse, do, calls=BWD_CALLS)
            except Exception as e:      # say so and go on
                print(f"{what}: does not compile: "
                      f"{' '.join(str(e).split())[:200]}", flush=True)
                continue
            print(f"{what}: {ms:8.4f} ms a call, "
                  f"{100 * flops / PEAK / (ms * 1e-3):5.1f} % of the peak",
                  flush=True)


def choice(readings):
    """The fastest pair of blocks, or the smallest (by the keys and
    queries a step holds) of those within `WITHIN` of it."""
    best = min(readings.values())
    near = [b for b, ms in readings.items() if ms <= best * (1 + WITHIN)]
    return min(near, key=lambda b: (b[0] * b[1], b[0]))


def pairs(text):
    return tuple(tuple(int(n) for n in p.split("x"))
                 for p in text.split(","))


def cell(readings, blocks):
    ms = readings.get(blocks)
    return (f"{blocks[0]} x {blocks[1]} "
            f"({'not read' if ms is None else f'{ms:.4f}'})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--latent", action="store_true",
                    help="the three latent classes' shapes, and the table")
    ap.add_argument("--dense", action="store_true",
                    help="the dense class's shape over its buckets, and "
                    "the table")
    ap.add_argument("--backward", action="store_true",
                    help="the backward kernel at the training cell's shapes")
    ap.add_argument("--parent", help="with --backward: a tree of another "
                    "commit, whose backward is read first")
    ap.add_argument("--heads", type=int, default=20)
    ap.add_argument("--kv-heads", type=int, help="default: the heads")
    ap.add_argument("--d", type=int, default=256, help="width of a key")
    ap.add_argument("--dv", type=int, default=256, help="width of a value")
    ap.add_argument("--buckets", help="default: 256 to 16,384, with "
                    "--dense to 4096")
    ap.add_argument("--blocks", default=",".join(
        f"{q}x{k}" for q, k in BLOCKS), help="block_q x block_k, ...")
    opts = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU")
    if opts.backward:
        print(f"device {jax.devices()[0].device_kind}; the backward, causal, "
              f"bfloat16, {BWD_CALLS} calls a program")
        return sweep_backward(BWD_LENGTHS, opts.parent)
    print(f"device {jax.devices()[0].device_kind}; causal, batch 1, "
          f"bfloat16, {LAYERS} calls a program")
    buckets = ([int(b) for b in opts.buckets.split(",")] if opts.buckets
               else DENSE_BUCKETS if opts.dense else BUCKETS)
    # the shapes, and the constant their class holds (its name, the pair)
    if opts.latent:
        shapes, held_as = LATENT, ("PREFILL_BLOCKS", PREFILL_BLOCKS)
    elif opts.dense:
        shapes, held_as = DENSE, ("FULL_BLOCKS", FULL_BLOCKS)
    else:
        shapes, held_as = {"as asked": (
            opts.heads, opts.kv_heads or opts.heads, opts.d, opts.dv)}, None
    table = {name: sweep(*shape, buckets, pairs(opts.blocks))
             for name, shape in shapes.items()}
    if held_as is None:
        return
    print(f"\nbucket: fastest (ms), chosen (ms), {held_as[0]} cut to the "
          "bucket (ms), 128 x 128 (ms)")
    for name, shape in shapes.items():
        for s, readings in table[name].items():
            if not readings:
                continue
            pick = choice(readings)
            held = tuple(min(b, s) for b in held_as[1])
            print(f"{name} ({shape_name(*shape)}) {s:5d}: "
                  f"{cell(readings, min(readings, key=readings.get))}, "
                  f"{cell(readings, pick)}, {cell(readings, held)}"
                  f"{'' if held == pick else ' [differs]'}, "
                  f"{cell(readings, (min(128, s),) * 2)}")


if __name__ == "__main__":
    main()
