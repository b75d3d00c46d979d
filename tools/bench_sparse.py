"""The pieces of the learned sparse attention (`ops/sparse_attention.py`) on
the chip, alone, at GLM-5's widths (32 lanes, 64 heads over latent rows of
640 that hold 576 numbers, 32 index heads of 128, the 2,048 best kept):

    chiprun -- python tools/bench_sparse.py            # both halves
    chiprun -- python tools/bench_sparse.py --only decode --lengths 3000,6000
    chiprun -- python tools/bench_sparse.py --only prefill --buckets 4096
    chiprun -- python tools/bench_sparse.py --only decode --run 1,4,8,16
    chiprun -- python tools/bench_sparse.py --only decode --widths dots3
    chiprun -- python tools/bench_sparse.py --only decode --widths dots3 \
        --fixed 34 --run 1,8 --lengths 3000,6000,12000

A decode step's three pieces a layer, each both ways (the index scores as
a gather of every table entry and as the walk over live pages, the choice
as `lax.top_k` and as a threshold, the attention as a gather of the chosen
rows and as the walk that reads every live row and keeps the chosen),
beside the dense latent kernel over the same lanes, every lane at one
length; with `--run` the two walks again at each run length, the tables
laid out in aligned runs of that many consecutive pages, the runs
shuffled (`walk_index_ms` / `walk_attend_ms` a row, and the ns a copy
their slope over the lengths gives; the other pieces, which no run
touches, are timed at the first run alone; with `--fixed` a lane's first
that many table entries are single pages of a fixed class, in any order,
the runs open behind them and the walks are told so, as a class that keeps
a ring or a slot lays its tables: `walk_*_ns_a_page` a row); a prefill's
three (the index-score kernel, the bisection that makes the mask, the
masked flash forward) beside the dense causal flash forward at the latent
classes' blocks. Milliseconds a layer, the median of `--reps` calls, each
call `LAYERS` layers in one program so a dispatch is not what is timed.
Chip only.
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.latent import PREFILL_BLOCKS
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops import sparse_attention as sa
from ray_tpu.ops.attention import flash_attention

PAGE, LAYERS = 16, 4
LANES, HEADS, ROW, LATENT = 32, 64, 640, 512
IDX_HEADS, IDX_DIM, TOPK = 32, 128, 2048
QK, V = 256, 256
SCALE = QK ** -0.5
# (heads, index heads, a prefill's key width, its value width) by `--widths`:
# GLM-5's, and dots3-note-prev's full layers' (the same rows of 640)
WIDTHS = {"glm-5": (HEADS, IDX_HEADS, QK, V), "dots3": (128, 64, 192, 128)}


def timed(fn, *args, reps=5):
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    del out
    return 1e3 * float(np.median(times)) / LAYERS


def chained(piece):
    """`LAYERS` calls of `piece(arrays, layer, carry)` in one program, each
    hanging on the one before through a scalar; the arrays are arguments
    (a pool captured as a constant would be compiled into the program)."""
    def run(arrays):
        carry = jnp.float32(0)
        for layer in range(LAYERS):
            carry = carry * 0 + jnp.sum(piece(arrays, layer, carry)).astype(
                jnp.float32)
        return carry
    return jax.jit(run)


def run_tables(max_pages, run, fixed=0):
    """Tables (LANES, `run_table_pages`) as the allocator lays them: a
    lane's first `fixed` entries single pages of the fixed class (ids
    under `LANES x fixed`), the rest aligned runs of `run` consecutive ids
    behind that class, singles and runs in any order (`run` 1: pages)."""
    rng = np.random.default_rng(0)
    width = pa.run_table_pages(max_pages, fixed, run)
    first = -(-LANES * fixed // run) * run
    starts = first + rng.permutation(LANES * (width - fixed) // run) * run
    return jnp.asarray(np.concatenate([
        rng.permutation(LANES * fixed).reshape(LANES, fixed),
        (starts[:, None] + np.arange(run)).reshape(LANES, width - fixed)],
        axis=1).astype(np.int32))


def decode(lengths, context, reps, runs=(1,), fixed=0):
    max_pages = context // PAGE
    pages = 1 + max(int(run_tables(max_pages, run, fixed).max())
                    for run in runs)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    pool = jax.random.normal(ks[0], (LAYERS, pages, PAGE, ROW),
                             jnp.bfloat16)
    idx_pool = jax.random.normal(ks[1], (LAYERS, pages, PAGE, IDX_DIM),
                                 jnp.bfloat16)
    q = jax.random.normal(ks[2], (LANES, HEADS, ROW), jnp.bfloat16)
    q_idx = jax.random.normal(ks[3], (LANES, IDX_HEADS, IDX_DIM),
                              jnp.bfloat16)
    w = jax.random.normal(ks[4], (LANES, IDX_HEADS), jnp.float32)
    bf = jnp.bfloat16

    def scores(a, layer, c):
        return sa.index_scores_paged(a["q_idx"] + c.astype(bf), a["w"],
                                     a["idx_pool"], layer, a["live"],
                                     a["lens"])

    def choose(a, layer, c):
        return sa.select_topk(a["scores"] + c, TOPK)[0]

    def attend(a, layer, c):
        return sa.mla_selected_attention(
            a["q"] + c.astype(bf), a["pool"], layer, a["live"], a["pos"],
            a["chosen"], LATENT, SCALE)

    def dense(a, layer, c):
        return pa.mla_paged_decode_attention(
            a["q"] + c.astype(bf), a["pool"], layer, a["live"], a["lens"],
            LATENT, SCALE)

    def walk_scores(run, a, layer, c):
        return sa._paged_index_call(a["q_idx"] + c.astype(bf), a["w"],
                                    a["idx_pool"], layer, a["live"],
                                    a["lens"], False, run, fixed)

    def threshold(a, layer, c):
        return sa.keep_topk(a["scores"] + c, TOPK)

    def walk_attend(run, a, layer, c):
        return sa._paged_attend_call(
            a["q"] + c.astype(bf), a["pool"], layer, a["live"], a["lens"],
            a["keep"], LATENT, SCALE, False, run, fixed)

    once = {"index_ms": chained(scores), "choice_ms": chained(choose),
            "attend_ms": chained(attend),
            "threshold_ms": chained(threshold),
            "dense_kernel_ms": chained(dense)}
    rows = []
    for run in runs:
        tables = run_tables(max_pages, run, fixed)
        pieces = {"walk_index_ms": chained(functools.partial(walk_scores,
                                                             run)),
                  "walk_attend_ms": chained(functools.partial(walk_attend,
                                                              run)),
                  **(once if run == runs[0] else {})}
        for n in lengths:
            a = {"pool": pool, "idx_pool": idx_pool, "q": q, "q_idx": q_idx,
                 "w": w, "lens": jnp.full((LANES,), n, jnp.int32),
                 "live": jnp.where(
                     jnp.arange(tables.shape[1])[None, :] * PAGE < n, tables,
                     -1)}
            a["scores"] = jax.jit(lambda a: scores(a, 0, jnp.float32(0)))(a)
            a["pos"], a["chosen"] = jax.jit(
                lambda s: sa.select_topk(s, TOPK))(a["scores"])
            a["keep"] = jax.jit(lambda s: sa.keep_topk(s, TOPK))(a["scores"])
            row = {"run": run, "length": n,
                   **{name: timed(fn, a, reps=reps)
                      for name, fn in pieces.items()}}
            if fixed:
                row.update(fixed=fixed, **{
                    name.replace("_ms", "_ns_a_page"):
                    1e6 * row[name] / (LANES * -(-n // PAGE))
                    for name in ("walk_index_ms", "walk_attend_ms")})
            rows.append(row)
            print(json.dumps(row), flush=True)
    for run in runs:            # ns a copy: the walks' slopes over length
        mine = [r for r in rows if r["run"] == run]
        if len(mine) > 1:
            pages = [-(-r["length"] // PAGE) for r in mine]
            copies = [LANES * (min(n, fixed) + -(-max(n - fixed, 0) // run))
                      for n in pages]
            print(json.dumps({"run": run, **{
                name.replace("_ms", "_ns_a_copy"): 1e6 * float(np.polyfit(
                    copies, [r[name] for r in mine], 1)[0])
                for name in ("walk_index_ms", "walk_attend_ms")}}),
                flush=True)
    return rows


def prefill(buckets, reps):
    bf = jnp.bfloat16
    rows = []
    for s in buckets:
        ks = jax.random.split(jax.random.PRNGKey(s), 6)
        a = {"q_idx": jax.random.normal(ks[0], (s, IDX_HEADS, IDX_DIM), bf),
             "k_idx": jax.random.normal(ks[1], (s, IDX_DIM), bf),
             "w": jax.random.normal(ks[2], (s, IDX_HEADS), jnp.float32),
             "q": jax.random.normal(ks[3], (HEADS, s, QK), bf),
             "k": jax.random.normal(ks[4], (HEADS, s, QK), bf),
             "v": jax.random.normal(ks[5], (HEADS, s, V), bf)}
        n = min(sa.SELECT_ROWS, s)

        def scores(a, layer, c):
            return sum(jnp.sum(sa._index_scores_call(
                a["q_idx"][b * n:(b + 1) * n] + c.astype(bf),
                a["w"][b * n:(b + 1) * n], a["k_idx"], b * n,
                False)[:, :8]) for b in range(s // n))

        def mask(a, layer, c):
            return sa.prefill_keep_mask(a["q_idx"] + c.astype(bf), a["w"],
                                        a["k_idx"], TOPK)

        def flash(a, layer, c):
            return sa.masked_flash_attention(a["q"] + c.astype(bf), a["k"],
                                             a["v"], a["keep"], SCALE)

        def dense(a, layer, c):
            return flash_attention(
                (a["q"] + c.astype(bf))[None], a["k"][None], a["v"][None],
                causal=True, sm_scale=SCALE, block_q=PREFILL_BLOCKS[0],
                block_k=PREFILL_BLOCKS[1])

        a["keep"] = jax.jit(lambda a: mask(a, 0, jnp.float32(0)))(a)
        row = {"bucket": s, "kept_a_row": float(jnp.mean(jnp.sum(
                   a["keep"].astype(jnp.int32), axis=1))),
               "index_kernel_ms": timed(chained(scores), a, reps=reps),
               "mask_ms": timed(chained(mask), a, reps=reps),
               "masked_flash_ms": timed(chained(flash), a, reps=reps),
               "dense_flash_ms": timed(chained(dense), a, reps=reps)}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del a
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=("decode", "prefill"))
    ap.add_argument("--lengths", default="2048,3000,6000,12000,16384")
    ap.add_argument("--context", type=int, default=16384)
    ap.add_argument("--buckets", default="4096,8192,16384")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--run", default="1",
                    help="pages a copy of the two walks, comma-separated")
    ap.add_argument("--fixed", type=int, default=0,
                    help="table entries of a fixed class ahead of the runs")
    ap.add_argument("--widths", choices=sorted(WIDTHS), default="glm-5",
                    help="whose heads and head widths")
    a = ap.parse_args()
    global HEADS, IDX_HEADS, QK, V, SCALE
    HEADS, IDX_HEADS, QK, V = WIDTHS[a.widths]
    SCALE = QK ** -0.5
    if jax.default_backend() != "tpu":
        sys.exit("bench_sparse.py measures the chip; there is none here")
    out = {}
    if a.only != "prefill":
        out["decode"] = decode([int(x) for x in a.lengths.split(",")],
                               a.context, a.reps,
                               [int(x) for x in a.run.split(",")], a.fixed)
    if a.only != "decode":
        out["prefill"] = prefill([int(x) for x in a.buckets.split(",")],
                                 a.reps)
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "chiprun_out", "bench_sparse.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
