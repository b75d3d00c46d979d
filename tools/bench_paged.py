"""The three paged decode kernels on the chip, alone: `paged_decode_attn`,
`paged_window_decode_attn` and `mla_paged_decode_attn` at the widths, lanes
and table lengths of the serving cells, every lane at one length or
(`--lanes mixed`) at lengths that differ, empty lanes among them, 24 calls
(layers) a program. Prints milliseconds a layer and the share of the bytes
`required_ops.paged_decode_call` says the algorithm needs, at 819 GB/s;
then, a shape and block, what a lane and layer costs by its pages, its
blocks and its first block's pages (`fit`: the last is the copy that
nothing hides, a lane's before PR 48 and a call's first lane's since).
Chip only:

    chiprun -- python tools/bench_paged.py
    chiprun -- python tools/bench_paged.py --sweep        # every block
    chiprun -- python tools/bench_paged.py --shapes pr27,laguna-window
    chiprun -- python tools/bench_paged.py --lanes mixed  # unlike lanes
    chiprun -- python tools/bench_paged.py --run 1,2,4,8  # pages a copy
    chiprun -- python tools/bench_paged.py --fixed        # behind a slot

`--sweep` puts each block size (in pages) in the place of
`walk_block_pages`'s answer: what `WALK_BUFFER_BYTES` and `BLOCK_POSITIONS`
in `ops/paged_attention.py` were chosen from (PERF.md section 6, PR 38);
`--prefixes each` lets the matmuls of a block reach every whole number of
pieces in the place of `walk_prefixes`'s few, `--heads n` puts `n` in the
place of `HEAD_UNROLL`. `--run` is the latent kernel alone (the shapes
"glm" and "longcat", at 1,024 / 2,048 / 4,096 positions a lane unless
`--positions` says otherwise) with its tables laid in aligned runs of that
many pages, the runs shuffled, and a run a copy: ms a layer and, from their
slope over the pages walked, the ns a page and a copy that
`models.latent.LatentAttention.page_run`'s constant was read from (PERF.md
section 6, PR 64). `--fixed` is `--run` at the four classes that keep a
state or tail slot ("ling", "falcon", "lfm2", "nemotron": `FIXED` has
each one's run and lane lengths), a lane's first table entry a single page
of the fixed class and whole runs behind it, run 1 against the class's run
(PERF.md section 6, PR 66). A tree from before PR 38 has two constants and no
rule: copy this file into its `tools/` and run it there to set parent
beside change in one call (`--json` writes the rows).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.required_ops import paged_decode_call
from ray_tpu.ops import paged_attention as pa

PAGE, HD, LAYERS, HBM = 16, 128, 24, 819e9
POSITIONS = (0, 16, 577, 1180, 3400, 4096)
# name: (kernel, lanes, query heads, kv heads, table pages, window);
# the latent kernel: 20 heads over rows of 640 that hold 576 numbers
SHAPES = {
    "pr27": ("full", 8, 16, 8, 256, 0),             # internlm2's cells
    "laguna-full": ("full", 32, 48, 8, 512, 0),
    "laguna-window": ("window", 32, 64, 8, 33, 512),
    "olmo": ("full", 32, 30, 30, 192, 0),
    "glm": ("latent", 32, 20, 1, 256, 0),
    "longcat": ("latent", 32, 64, 1, 256, 0),       # 64 query rows a lane
    # the classes that keep a slot: their table's first entry (`FIXED`)
    "ling": ("latent", 32, 32, 1, 1024, 0),         # docs16k
    "falcon": ("full", 32, 20, 4, 160, 0),          # chat2k: 16 KB a pool
    # turns4k: 8 kv heads of 64, two a 128-lane, as the kernel reads them
    "lfm2": ("full", 64, 32, 4, 256, 0),
    "nemotron": ("full", 32, 32, 2, 512, 0),        # agent8k: 8 KB a pool
}
RUN_POSITIONS = (1024, 2048, 4096)
# name: (fixed entries, the class's run, a lane's positions) under `--fixed`
FIXED = {"ling": (1, 4, (2048, 6144, 12288)),
         "falcon": (1, 4, (512, 1024, 2048)),
         "lfm2": (1, 4, (512, 1024, 3072)),
         "nemotron": (1, 8, (1024, 4096, 8000))}
LATENT, ROW, ROW_HELD = 512, 640, 576
# `--lanes mixed`: lane i is this share of the row's positions long, so a
# lane hands its walk on to a shorter one, a longer one and past empty ones
MIXED = (1.0, 0.25, 0.0, 1.0, 0.5, 0.0, 0.0, 0.75)


def program(kernel, window, run=None, fixed=0):
    """`LAYERS` calls of a kernel, each one's queries hanging on the one
    before: lengths and tables are data, so one program a shape and block
    (and a `run` behind `fixed` table entries)."""
    # (by keyword and only where asked: an older tree's kernel takes none)
    runs = {} if run is None else {"run": run}
    if fixed:
        runs["fixed"] = fixed

    def step(q, tables, lens, *pools):
        for _ in range(LAYERS):
            if kernel == "latent":
                out = jnp.pad(pa.mla_paged_decode_attention_kernel(
                    q, *pools, 0, tables, lens, LATENT, 0.07, **runs),
                    ((0, 0), (0, 0), (0, ROW - LATENT)))
            elif window:
                out = pa.paged_window_decode_attention_kernel(
                    q, *pools, 0, tables, lens, window)
            else:
                out = pa.paged_decode_attention_kernel(q, *pools, 0, tables,
                                                       lens, **runs)
            q = q + out * 1e-3
        return q
    return jax.jit(step)


def pools_of(kernel, lanes, heads, kvh, table):
    """(queries, pools): a pool holds a whole table a lane (and a run
    more: the fixed class is no whole runs)."""
    key = jax.random.PRNGKey(0)
    table += 1
    if kernel == "latent":
        return (jax.random.normal(key, (lanes, heads, ROW), jnp.bfloat16),
                (jax.random.normal(key, (1, lanes * table, PAGE, ROW),
                                   jnp.bfloat16),))
    k = jax.random.normal(key, (1, lanes * table, PAGE, kvh * HD),
                          jnp.bfloat16)
    return (jax.random.normal(key, (lanes, heads, HD), jnp.bfloat16),
            (k, k[:, ::-1]))


def lanes_at(kernel, lanes, heads, kvh, table, window, length,
             mixed=False, run=1, fixed=0):
    """(tables, lengths, bytes the algorithm needs a layer): every lane
    `length` long, or `MIXED`'s shares of it in turn, its pages anywhere
    in the pool: a page at a time or, as the allocator hands them out at
    `run`, in whole aligned runs of `run` ids behind one another, behind
    `fixed` single pages of the fixed class (ids under `lanes x fixed`;
    the table then `run_table_pages` wide)."""
    lens = np.full((lanes,), length, np.int32)
    if mixed:
        lens = (length * np.resize(MIXED, lanes)).astype(np.int32)
    rng = np.random.default_rng(length)
    first = -(-lanes * fixed // run) * run
    width = pa.run_table_pages(table, fixed, run) if fixed else table
    free = first + rng.permutation(
        lanes * (width - fixed) // run).astype(np.int32) * run
    heads_ = rng.permutation(lanes * fixed).astype(np.int32)
    tables = np.full((lanes, width), -1, np.int32)
    for lane, n in enumerate(lens):
        pages = min(-(-int(n) // PAGE), table)
        held = min(pages, fixed)
        tables[lane, :held] = heads_[lane * fixed:lane * fixed + held]
        runs = -(-(pages - held) // run)
        mine, free = free[:runs], free[runs:]
        tables[lane, fixed:fixed + runs * run] = (
            mine[:, None] + np.arange(run)[None]).reshape(-1)
    live = int((np.minimum(lens, window) if window else lens).sum())
    if kernel == "latent":
        need = paged_decode_call(live, lanes, 1, ROW_HELD // 2,
                                 heads * (ROW_HELD + LATENT) // 2)
    else:
        need = paged_decode_call(live, lanes, 1, kvh * HD, heads * HD)
    return jnp.asarray(tables), jnp.asarray(lens), need["bytes"]


def fit(rows, lanes, block):
    """Microseconds a lane and layer as `lane + page * pages + block *
    blocks + first * (pages of the first block)`, least squares over the
    rows of one shape, in blocks of `block` pages, whose lanes are all one
    length: `first` is what a page of a copy that nothing hides costs.
    None under five lengths."""
    pages = np.array([r["pages"] for r in rows])
    if len(set(pages)) < 5:
        return None
    terms = np.stack([np.ones_like(pages), pages, -(-pages // block),
                      np.minimum(pages, block)], axis=1).astype(float)
    us = np.array([r["ms_a_layer"] for r in rows]) * 1e3 / lanes
    lane, page, blk, first = (float(c) for c in np.linalg.lstsq(
        terms, us, rcond=None)[0])
    return {"block": block, "lane_us": lane, "page_us": page,
            "block_us": blk, "first_block_page_us": first,
            "first_block_us": first * block}


def timed(fn, *args):
    """Milliseconds a layer: the least of three batches of calls, each
    long enough (0.05 s or ten calls) to be read on the host's clock."""
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    jax.block_until_ready(fn(*args))
    n = max(10, int(0.05 / max(time.perf_counter() - t, 1e-5)))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / n)
    return best * 1e3 / LAYERS


def set_block(pages, prefixes):
    """`pages` a block in the place of what the tree would choose (None:
    as the tree stands); the rule's doubling or every piece."""
    jax.clear_caches()      # the jitted calls keep what they were traced with
    if not hasattr(pa, "walk_block_pages"):     # before PR 38
        if pages is not None:
            pa.BLOCK_PAGES = pa.MLA_BLOCK_PAGES = pages
        return
    if pages is not None:
        pa.walk_block_pages = lambda page_bytes, page_size, max_pages: min(
            pages, max_pages)
    if prefixes == "each":
        piece = pa.PIECE_POSITIONS // PAGE
        pa.walk_prefixes = lambda block, page_size: (
            *range(piece, block, piece), block)


def write(path, rows):
    if path:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(rows, f, indent=1)


def by_run(opts):
    """A kernel at each `--run` (under `--fixed`: at 1 and at its class's,
    behind the class's fixed entries): ms a layer by length, then the slope
    of the lanes' time over the pages they walk."""
    rows = []
    for name in opts.shapes.split(","):
        kernel, lanes, heads, kvh, table, window = SHAPES[name]
        fixed, own, positions = FIXED[name] if opts.fixed else (
            0, None, RUN_POSITIONS)
        if opts.positions:
            positions = [int(p) for p in opts.positions.split(",")]
        if window:
            raise SystemExit(f"--run is no ring's: {name} walks one")
        q, pools = pools_of(kernel, lanes, heads, kvh, table)
        for run in ((1, own) if opts.fixed else (
                int(r) for r in opts.run.split(","))):
            fn = program(kernel, window, run, fixed)
            mine = []
            for length in positions:
                tables, lens, need = lanes_at(*SHAPES[name], length, run=run,
                                              fixed=fixed)
                ms = timed(fn, q, tables, lens, *pools)
                mine.append({"shape": name, "run": run, "fixed": fixed,
                             "positions": length,
                             "pages": lanes * -(-length // PAGE),
                             "ms_a_layer": ms,
                             "bytes_share": need / HBM * 1e5 / ms})
                print(f"{name} run {run} positions {length}: {ms:.4f} ms a "
                      f"layer, {mine[-1]['bytes_share']:.1f} % of the "
                      f"bytes' floor", flush=True)
            rows += mine
            if len(mine) > 1:
                ns = 1e6 * float(np.polyfit(
                    [r["pages"] for r in mine],
                    [r["ms_a_layer"] for r in mine], 1)[0])
                rows.append({"shape": name, "run": run, "fixed": fixed,
                             "ns_a_page": ns, "ns_a_copy": ns * run})
                print(f"{name} run {run}: {ns:.1f} ns a page, "
                      f"{ns * run:.1f} a copy", flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--blocks", default="8,16,32,64,128",
                    help="pages a block, under --sweep")
    ap.add_argument("--shapes", help="comma-separated, of " + ",".join(
        SHAPES) + " (all; under --run the latent ones)")
    ap.add_argument("--positions", help="comma-separated lengths of a "
                    "lane, in place of " + ",".join(map(str, POSITIONS)))
    ap.add_argument("--lanes", choices=("same", "mixed"), default="same",
                    help="every lane at a row's positions, or at "
                    "MIXED's shares of them")
    ap.add_argument("--prefixes", choices=("rule", "each"), default="rule")
    ap.add_argument("--heads", type=int, help="heads a turn of the loop "
                    "over a block's heads, in place of HEAD_UNROLL")
    ap.add_argument("--run", help="pages a copy of a kernel's walk, "
                    "comma-separated: its tables in such runs")
    ap.add_argument("--fixed", action="store_true", help="the classes that "
                    "keep a slot (FIXED), at run 1 and at their own run")
    ap.add_argument("--json", help="write the rows here too")
    opts = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU")
    opts.shapes = opts.shapes or ",".join(
        FIXED if opts.fixed else (
            name for name, shape in SHAPES.items() if name not in FIXED and (
                not opts.run or shape[0] == "latent")))
    if opts.run or opts.fixed:
        return write(opts.json, by_run(opts))
    blocks = [int(b) for b in opts.blocks.split(",")] if opts.sweep else [
        None]
    positions = [int(p) for p in opts.positions.split(",")] \
        if opts.positions else POSITIONS
    if opts.heads:
        pa.HEAD_UNROLL = opts.heads
    rows = []
    for name in opts.shapes.split(","):
        kernel, lanes, heads, kvh, table, window = SHAPES[name]
        q, pools = pools_of(kernel, lanes, heads, kvh, table)
        done = set()
        for block in blocks:
            if block is not None:
                block = min(block, table)   # as the call would cap it
                if block in done:
                    continue
                done.add(block)
            set_block(block, opts.prefixes)
            fn = program(kernel, window)
            mine = []
            for length in positions:
                if length > table * PAGE and not window:
                    continue
                tables, lens, need = lanes_at(*SHAPES[name], length,
                                              opts.lanes == "mixed")
                try:
                    ms = timed(fn, q, tables, lens, *pools)
                except Exception as e:      # say so and go on
                    print(f"{name} block {block} positions {length}: "
                          f"failed: {str(e)[:300]}", flush=True)
                    continue
                share = need / HBM * 1e5 / ms
                mine.append({"shape": name, "block": block,
                             "lanes": opts.lanes, "positions": length,
                             "pages": int((tables[0] >= 0).sum()),
                             "ms_a_layer": ms, "bytes_share": share})
                print(f"{name} ({kernel}, {lanes} lanes {opts.lanes}) "
                      f"block {block or 'as it stands'} positions "
                      f"{length}: {ms:.4f} ms a layer, {share:.1f} % of "
                      f"the bytes' floor", flush=True)
            rows += mine
            terms = mine and opts.lanes == "same" and hasattr(
                pa, "walk_block_pages") and fit(
                mine, lanes, block or pa.walk_block_pages(sum(
                    PAGE * p.shape[3] * p.dtype.itemsize for p in pools),
                    PAGE, table))
            if terms:
                rows.append({"shape": name, "fit": terms})
                print(f"{name} block {terms['block']} fit, us a lane and "
                      f"layer: {terms['lane_us']:.2f} + "
                      f"{terms['page_us']:.4f} a page + "
                      f"{terms['block_us']:.2f} a block + "
                      f"{terms['first_block_page_us']:.4f} a page of the "
                      f"first block ({terms['first_block_us']:.2f} when "
                      f"it is full)", flush=True)
    write(opts.json, rows)


if __name__ == "__main__":
    main()
