"""Paged decode attention: one query token per lane against the KV pages
the lane holds, as a Pallas TPU kernel.

The serving engine's cache is a pool of pages `(layers, pages,
page_size, kv_heads * head_dim)` (`models.decode.init_paged_cache`); a
lane owns the pages its table lists, in any order. The decode step's
attention has to move what the lanes hold and no more: the kernel leaves
the pool in HBM, takes page tables, lengths and the layer index by scalar
prefetch, and copies in only the live pages of each lane, one DMA a page
(a page is contiguous, and a kv head is whole lanes of its rows, or a
whole share of one 128-lane: `LANE`, "A head narrower than a 128-lane"
below), double-buffered in blocks whose size comes from the bytes of a page
(`walk_block_pages`); a block is multiplied over the part of it the lane
holds (`walk_prefixes`). The buffers outlive a lane: behind its last block
a lane starts the first block of the lane after it, so the copies stop at
a call's edge and not at a lane's (`walk_first_blocks_hidden`). Lengths
are data: one compiled program whatever the lanes hold.

Numerics follow `attention._flash_fwd_kernel`: keys and values stay in
the pool's dtype for the MXU, scores, running max and sum and the
accumulator are float32, probabilities are rounded to the value dtype for
the second matmul. The `n_heads // kv_heads` query heads of a kv head are
one matmul's rows.

`paged_attention_reference` is the gather + masked einsum the decode step
ran before: the off-TPU path and the ground truth of the tests, which
reach the kernel through the Pallas interpreter
(`paged_decode_attention_kernel`).

The latent pool of `models.mla_moe` has its own kernel at the end of this
file, `mla_paged_decode_attn`: a position is one row `[c_kv | k_rope |
padding]` shared by every head, read once and used as the key (all of it)
and as the value (its first `latent` numbers). Both kernels stand on one
page walk (`_walk_pages`) and differ in their matmuls.

A layer with a sliding window keeps a lane's last pages only, in a ring:
logical page j of a lane lies at entry `j mod ring` of its table, and what
fell out of the window is overwritten. `paged_window_decode_attn` is the
per-head kernel on the same walk, begun at the first page the window
reaches, its table wrapped, positions below the window masked.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import DEFAULT_MASK_VALUE
from ray_tpu.ops.dispatch import on_tpu, shard_kernel

# The kernel's name on the device's clock (see attention.KERNEL_FWD).
KERNEL_PAGED_DECODE = "paged_decode_attn"

# Blocks in VMEM at once: one attended to, the next on its way, be that the
# lane's next or the next lane's first (more bought nothing on a v5e: a
# block's matmuls, not its copies, take longest).
BLOCK_SLOTS = 2
# A block costs a v5e a fixed time whatever it holds (each head's chain of
# score matmul, max, exp, sum, value matmul and rescale: half a microsecond
# at 8 kv heads), so it is as large as two things let it be, both chosen on
# the chip (`tools/bench_paged.py --sweep`; PERF.md section 6, PR 38): the
# bytes of VMEM the blocks' buffers may take, all slots and pools together
# (what bounds a page of 30 kv heads) ...
WALK_BUFFER_BYTES = 16 << 20
# ... and its positions (what bounds a page of 8 kv heads, or of one latent
# row). Until PR 48 a lane waited for its first block with nothing to
# multiply in front of it, and past these that wait outweighed the blocks
# saved. Now the lane before starts it, and blocks of 2,048 positions read
# 2-13 % better from 1,180 positions a lane up; what holds the constant is
# `walk_prefixes`: a lane of 257-512 positions would be multiplied at such
# a block's half, 1,024, and reads 16-18 % worse (PERF.md section 6, PR 48).
BLOCK_POSITIONS = 1024
# Copies a turn of the loop that starts, or waits for, a block's copies: a
# page each, or a run of pages where the walk is told its tables are laid
# in runs (unrolled, the scalar core overlaps their table reads and
# descriptors; a descriptor costs it some 50 ns whatever it brings, which
# is what bounds a walk whose copies are under some 64 KB: a page of 8-20
# KB of a pool, PERF.md section 6, PR 62 and PR 64).
COPY_UNROLL = 4
# Heads a turn of the loop over a block's heads multiplies, unrolled (as
# many, up to these, as divide the kv heads). A head's chain of matmul, max,
# exp, sum, matmul hides behind its neighbour's only inside one turn (one
# head a turn: 15-25 % slower); but every unrolled head is a copy of the
# matmuls in the program, which a replica traces once and lowers once a
# layer at set-up (30 heads unrolled at two lengths: 3.5 s of it; in 5
# turns of 6: none, and 4 % of the kernel; PERF.md section 6, PR 38).
HEAD_UNROLL = 8
# Positions a block is multiplied in whole pieces of: the keys of a piece
# are one 128 x 128 tile of the MXU a head.
PIECE_POSITIONS = 128


def walk_block_pages(page_bytes: int, page_size: int, max_pages: int) -> int:
    """Pages a block of the walk holds: as many whole pieces as
    `BLOCK_POSITIONS` hold and as fit `WALK_BUFFER_BYTES` at `page_bytes`
    a page (all pools together) in `BLOCK_SLOTS` slots, one piece at
    least, and no more than the table's `max_pages`."""
    piece = max(1, PIECE_POSITIONS // page_size)
    fit = min(WALK_BUFFER_BYTES // (BLOCK_SLOTS * page_bytes),
              BLOCK_POSITIONS // page_size)
    return min(max(fit // piece, 1) * piece, max_pages)


@functools.lru_cache(maxsize=None)
def walk_prefixes(block_pages: int, page_size: int) -> tuple:
    """The lengths in pages, ascending, that the matmuls of a block reach:
    one piece, two, the block's half in whole pieces where that is more,
    and the block. A block of which the walk reaches `n` pages is
    multiplied as far as the least of these that is `>= n`: piece by piece
    up to two (what a walk in blocks of a piece would cost), else in one
    softmax update, so a full block is one and no lane pays more than
    twice what it holds. Few, because each is a copy of every head's
    matmuls in the program (a replica traces them at set-up)."""
    piece = min(max(1, PIECE_POSITIONS // page_size), block_pages)
    half = block_pages // 2 // piece * piece
    return tuple(sorted({n for n in (piece, 2 * piece, half)
                         if piece <= n < block_pages} | {block_pages}))


def walk_counts(pages: int, block_pages: int, page_size: int,
                pad: int = 0):
    """(blocks, positions multiplied) of a lane whose walk covers `pages`
    pages: what `_walk_pages` does, counted on the host. `pad`: the places
    of its first block that no entry lands on (`run_pad`), which a lane
    that holds a page walks with the rest."""
    full, rest = divmod(pages + pad if pages else 0, block_pages)
    tail = next((n for n in walk_prefixes(block_pages, page_size)
                 if n >= rest), 0) if rest else 0
    return full + bool(rest), (full * block_pages + tail) * page_size


def run_pad(fixed: int, run: int) -> int:
    """Places at the head of a walk's first block that no table entry
    lands on, where the tables' first `fixed` entries are of the fixed
    class and the runs of `run` open behind them: as many as bring entry
    `fixed` to a multiple of `run`, so that no run straddles a block."""
    return -fixed % run


def run_table_pages(pages: int, fixed: int, run: int) -> int:
    """Entries of a table whose sequences hold up to `pages` pages, the
    first `fixed` of the fixed class, the rest handed out in runs of `run`
    (`serve/llm/kv_cache.py`): the fixed entries and then whole runs, so
    that a sequence at full length fits with the last run it is handed,
    and every walk finds whole runs behind entry `fixed`. Without a fixed
    class a table is its `pages`, which its run divides
    (`copy_run_pages`)."""
    if not fixed:
        return pages
    return fixed + -(-max(pages - fixed, 0) // run) * run


def walk_first_blocks_hidden(pages) -> int:
    """Of a call's lanes, which hold `pages` pages each, those whose first
    block is on its way when their turn comes, started by the lane before
    them behind its own last block (`_walk_pages`): every lane that holds
    pages but the first such, whatever empty lanes lie between."""
    return max(sum(n > 0 for n in pages) - 1, 0)


# A head narrower than a 128-lane. The kernels slice a block of pages by
# whole 128-lanes: a head is `(positions, head_dim)` of the buffer with
# nothing moved. A kv head of 64 is half a lane, and its neighbour the
# other half. Such heads are multiplied `LANE // head_dim` at a time, as
# one head of 128 whose group is all their groups' query rows, each row
# zero-extended over its neighbours' part of the lane (as the latent
# kernel's query is `[... | zeros]`): a score is then the row's own head's
# dot product and nothing else; the values are read as the whole lane and
# of a row's output its own head's part is kept (`_lane_queries`,
# `_own_parts`). The pool is read once, as it lies, with no copy; the MXU
# multiplies `LANE // head_dim` times what the heads need, on a kernel the
# bytes bound. The kernels' bodies do not know: they are handed heads of
# 128.
LANE = 128


def paged_decode_tiles(head_dim: int, page_size: int, dtype,
                       kv_dim: int = 0) -> bool:
    """Whether the kernel tiles these shapes: a head is whole lanes of a
    pool row, or a whole share of one lane in a row of whole lanes
    (`kv_dim`, kv heads x head dim, where the caller knows it: an odd count
    of heads of 64 is refused, and its attention gathers), and a page is
    whole `(sublane, 128)` tiles of the pool's dtype, so that a page lands
    in VMEM with one contiguous copy and a block of pages reads as one
    `(positions, 128 k)` matrix a head, or a lane's heads."""
    sublanes = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    lanes = head_dim % LANE == 0 or (
        0 < head_dim < LANE and LANE % head_dim == 0
        and kv_dim > 0 and kv_dim % LANE == 0)
    return lanes and page_size % sublanes == 0


def _lane_queries(q, kvh: int):
    """q (B, n_heads, hd) -> the kernels' query (B, kv heads, group, hd),
    the `n_heads // kvh` query heads of a kv head one matmul's rows; where
    a head is a share of a lane, (B, kvh // pack, pack x group, 128): the
    `pack` heads of a lane as one, each row zeros outside its own head's
    part."""
    B, n_heads, hd = q.shape
    group = n_heads // kvh
    qg = q.reshape(B, kvh, group, hd)
    if hd >= LANE:
        return qg
    pack = LANE // hd
    qg = qg.reshape(B, kvh // pack, pack, group, 1, hd)
    own = jnp.eye(pack, dtype=q.dtype).reshape(1, 1, pack, 1, pack, 1)
    return (qg * own).reshape(B, kvh // pack, pack * group, LANE)


def _own_parts(out, n_heads: int, hd: int):
    """`_lane_queries` undone on the kernels' output: (B, n_heads, hd), of
    a row that read a lane of several heads its own head's part."""
    B = out.shape[0]
    if hd >= LANE:
        return out.reshape(B, n_heads, hd)
    pack = LANE // hd
    out = out.reshape(B, out.shape[1], pack, out.shape[2] // pack, pack, hd)
    return jnp.stack([out[:, :, j, :, j] for j in range(pack)],
                     axis=2).reshape(B, n_heads, hd)


# ------------------------------------------------------------- reference
def paged_attention_reference(q, k_pool, v_pool, layer, page_tables,
                              lengths):
    """Gather every table entry, mask, softmax in float32.

    q: (B, n_heads, hd); pools (layers, pages, page, kv * hd);
    page_tables (B, max_pages) int32, -1 unassigned; lengths (B,) int32:
    positions `< length` on assigned pages are visible.
    Returns (B, n_heads, hd) in q's dtype; a lane that sees nothing
    gets zeros.
    """
    span = page_tables.shape[1] * k_pool.shape[2]
    seen = jnp.arange(span)[None, :] < lengths[:, None]
    return _gathered_attention(q, k_pool, v_pool, layer, page_tables, seen)


def _gathered_attention(q, k_pool, v_pool, layer, tables, seen):
    """Attention of q (B, n_heads, hd) over every entry of `tables` (B,
    entries), gathered whole: `seen` (B, entries * page) says which of the
    gathered positions a lane sees, on assigned entries."""
    B, n_heads, hd = q.shape
    num_pages, page = k_pool.shape[1:3]
    kvh = k_pool.shape[3] // hd
    span = tables.shape[1] * page
    pt = jnp.clip(tables, 0, num_pages - 1)
    keys = k_pool[layer][pt].reshape(B, span, kvh, hd)
    vals = v_pool[layer][pt].reshape(B, span, kvh, hd)
    mask = seen & jnp.repeat(tables >= 0, page, axis=1)
    qg = q.reshape(B, kvh, n_heads // kvh, hd)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                        keys.astype(jnp.float32)) * (1.0 / hd ** 0.5)
    scores = jnp.where(mask[:, None, None, :], scores,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, vals.astype(jnp.float32))
    out = jnp.where(mask.any(axis=1)[:, None, None, None], out, 0.0)
    return out.astype(q.dtype).reshape(B, n_heads, hd)


# ----------------------------------------- the page walk of both kernels
#
# What the per-head kernel and the latent one (end of this file) share is
# everything but their matmuls: a lane's table read in order, its live
# pages copied in block by block behind the block being attended to, the
# mask of what a block holds, the online softmax, and the `pallas_call`
# around it. A kernel's body names its keys and values; the rest is here.
def _walk_pages(layer_ref, len_ref, pt_ref, o_ref, sems, acc_ref, m_ref,
                l_ref, finite_ref, hand_ref, copies, value_buf, attend, *,
                page_size: int, block_pages: int, max_pages: int,
                window: int = 0, run: int = 1, fixed: int = 0):
    """A paged decode kernel but for its matmuls: `attend(slot, start,
    pages, seen, rolled)` on every block of pages the grid's lane holds, in table
    order, between the reset of the running softmax and its division into
    `o_ref`. `slot`: the block's place in the buffers; `pages` (static)
    from page `start` of it: the part to multiply, a piece of the block or
    one of its `walk_prefixes`, which together hold all the lane holds of
    it; `seen` (1, positions of those pages): what the lane sees of
    them; `rolled`: the part is long enough for a kernel to loop over its
    heads and not unroll them (a head's chain of matmul, max, exp, sum,
    matmul hides behind the next head's only when both are in one basic
    block, which a piece needs and a whole block does not; and every
    unrolled head is a copy of the matmuls in the program, which a replica
    traces and lowers once a layer at set-up). `copies`: (pool in HBM, buffer, its semaphore's index after
    the slot's) a pool; `value_buf`: where the values are read;
    `finite_ref` (slots,) in SMEM: the walk's own note of how much of each
    slot of it holds numbers; `hand_ref` (2,) in SMEM: what a lane hands
    the lane behind it, the slot its first block goes to and whether that
    block is on its way already.
    With a `window` the lane sees its last `window` positions only and the
    table is a ring of `max_pages` entries (logical page j at entry j mod
    `max_pages`): the walk begins at the first page the window reaches.
    With a `run` above 1 the tables' owner vouches that a lane's pages lie
    in aligned runs of `run` (`serve/llm/kv_cache.py`: entry `k * run`
    names the first of `run` pages that lie behind one another in the pool,
    all the lane's own, those no position has reached yet too), and one
    copy brings a run: a run is copied whole where its first entry is held,
    and what of it lies past the lane's length is not `seen`. A run lies
    in one block (tables and blocks of whole runs), and not in a ring,
    whose walk begins at any entry.
    With `fixed` the tables' first `fixed` entries are pages of the
    allocator's fixed class, handed out one by one, and the runs open at
    entry `fixed`: those entries are copied a page a descriptor, the rest
    a run, and table entry `e` lies at place `e + run_pad(fixed, run)` of
    the walk, so that entry `fixed` lands on a multiple of `run` and a run
    in one block still; the first block's leading places, fewer than `run`,
    hold nothing and are not `seen`. At `run` 1 a table's head is walked
    as the rest is.

    The grid runs the lanes in order and the scratch outlives a grid step:
    behind its last block a lane has nothing of its own left to copy in,
    so it starts the first block of the next lane that holds pages, into
    the slot it is not multiplying from, and that lane finds it in flight
    (`walk_first_blocks_hidden` counts them). Only the first such lane of
    a call waits for a copy that nothing hides."""
    if run == 1:
        fixed = 0
    if run > 1 and (window or (max_pages - fixed) % run
                    or block_pages % run):
        raise ValueError(
            f"a walk that copies runs of {run} pages takes tables and "
            f"blocks of whole runs and no ring: {max_pages} pages "
            f"({fixed} fixed) in blocks of {block_pages}, window {window}")
    pad = run_pad(fixed, run)
    head = fixed + pad              # the place the runs open at
    b = pl.program_id(0)
    lanes = len_ref.shape[0]
    slots = value_buf.shape[0]
    layer = layer_ref[0]

    def walk_of(lane):
        """(lane, the first page its walk covers, how many): whose pages
        a copy or a mask is about."""
        length = len_ref[lane]
        if window:
            first = jnp.maximum(length - window, 0) // page_size
            return lane, first, jnp.minimum(
                pl.cdiv(length, page_size) - first, max_pages)
        pages = jnp.minimum(pl.cdiv(length, page_size), max_pages)
        if pad:
            pages = jnp.where(pages > 0, pages + pad, 0)
        return lane, 0, pages

    me = walk_of(b)
    length, first, n_blocks = len_ref[b], me[1], pl.cdiv(me[2], block_pages)
    # the next lane that holds pages (`lanes`: none does), for its table;
    # a lane that holds none hands nothing on and does not look
    behind = lax.cond(n_blocks > 0, lambda: lax.while_loop(
        lambda n: (n < lanes) & (walk_of(jnp.minimum(n, lanes - 1))[2] <= 0),
        lambda n: n + 1, b + 1), lambda: jnp.int32(lanes))
    after = walk_of(jnp.minimum(behind, lanes - 1))

    def page_at(who, blk, p):
        """(table entry, whether the lane holds a page there) of page `p`
        of block `blk` of the walk `who`, one it reaches."""
        lane, first, _ = who
        idx = blk * block_pages + p
        if window:
            idx = (first + idx) % max_pages
        if pad:
            idx = idx - pad
        page = pt_ref[lane * max_pages + idx]
        return page, page >= 0

    def reach_of(who, blk):
        """Pages of block `blk` the walk `who` reaches."""
        return jnp.minimum(who[2] - blk * block_pages, block_pages)

    prefixes = walk_prefixes(block_pages, page_size)
    piece = prefixes[0]

    def first_place(blk):
        """The first place of block `blk` an entry lands on."""
        return jnp.clip(pad - blk * block_pages, 0, block_pages)

    def each_copy(who, blk, slot, act):
        """`act` on the copy of every page of block `blk` the lane of
        `who` holds, into `slot` (a loop, not unrolled: a block of 64
        pages is traced as one page, and a short lane pays for the pages
        it has); a copy a page, or one a run of `run` pages whose first
        the lane holds (behind a table's `fixed` head, a page each).
        Returns how many pages the walk reaches those were."""
        reach = reach_of(who, blk)

        def loop(step, first, end, held, unroll=COPY_UNROLL):
            """The copies of `step` pages each from place `first` (None:
            the block's first) up to `end`, `unroll` a turn."""
            def some(i, held):
                for p in range(unroll):
                    p = i * unroll + p
                    if step > 1:                # the run's first page
                        p = p * step
                    if first is not None:
                        p = first + p
                    page, live = page_at(who, blk, p)
                    live = live & (p < end)

                    @pl.when(live)
                    def _():
                        for pool, buf, sem in copies:
                            if step == 1:
                                src, dst = (pool.at[layer, page],
                                            buf.at[slot, p])
                            else:
                                src = pool.at[layer, pl.ds(
                                    pl.multiple_of(page, step), step)]
                                dst = buf.at[slot, pl.ds(
                                    pl.multiple_of(p, step), step)]
                            act(pltpu.make_async_copy(
                                src, dst, sems.at[(slot, *sem)]))
                    if step == 1:
                        held = held + live.astype(jnp.int32)
                    else:
                        held = held + jnp.where(
                            live, jnp.minimum(end - p, step), 0)
                return held
            return lax.fori_loop(
                0, pl.cdiv(end if first is None else end - first,
                           unroll * step), some, held)

        if not fixed:
            return loop(run, None, reach, jnp.int32(0))
        runs = jnp.clip(head - blk * block_pages, 0, block_pages)
        held = loop(1, first_place(blk), jnp.minimum(runs, reach),
                    jnp.int32(0), min(COPY_UNROLL, fixed))
        return loop(run, runs, reach, held)

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(b == 0)
    def _():
        for slot in range(slots):
            finite_ref[slot] = 0
        hand_ref[0] = 0
        hand_ref[1] = 0

    def start(who, blk, slot):
        """Begin the copies of block `blk` of the walk `who` into `slot`.
        A page of the prefix the block will be multiplied at that is not
        copied in keeps what its buffer held, and a probability of 0 does
        not neutralise NaN (0 * NaN): so first the slot's values are made
        numbers that far, once a call (`finite_ref`: zeros, or later the
        values of an earlier block, this lane's or another's)."""
        reach = reach_of(who, blk)
        need = prefixes[-1]
        for pages in prefixes[-2::-1]:
            need = jnp.where(reach <= pages, pages, need)

        @pl.when(finite_ref[slot] < need)
        def _():
            under = 0
            for pages in prefixes:
                @pl.when((finite_ref[slot] <= under) & (need > under))
                def _(under=under, pages=pages):
                    part = value_buf.at[slot, under:pages]
                    part[:] = jnp.zeros_like(part)
                under = pages
            finite_ref[slot] = need
        each_copy(who, blk, slot, lambda copy: copy.start())

    # the lane's blocks go to the slots in turn from where the lane before
    # stopped; its first is on its way if that lane started it
    base, handed = hand_ref[0], hand_ref[1] == 1
    for ahead in range(slots - 1):
        mine = ahead < n_blocks
        @pl.when(mine if ahead else mine & ~handed)
        def _():
            start(me, ahead, (base + ahead) % slots)
    hand_ref[0] = (base + n_blocks) % slots
    hand_ref[1] = (handed & (n_blocks == 0)).astype(jnp.int32)

    def body(blk, carry):
        # behind the block being multiplied the next is copied in: this
        # lane's or, behind its last, the first of the lane after it
        ahead = blk + slots - 1
        own = ahead < n_blocks
        hand = (blk == n_blocks - 1) & (behind < lanes)

        @pl.when(own | hand)
        def _():
            start(tuple(jnp.where(own, mine, theirs)
                        for mine, theirs in zip(me, after)),
                  jnp.where(own, ahead, 0),
                  (base + jnp.where(own, ahead, n_blocks)) % slots)
            hand_ref[1] = hand.astype(jnp.int32)

        slot = (base + blk) % slots
        held = each_copy(me, blk, slot, lambda copy: copy.wait())
        # the block is multiplied over the shortest prefix that holds the
        # pages the walk reaches in it: what lies behind was not copied in
        # and is not read, what lies inside and is not live is not `seen`
        reach = reach_of(me, blk)
        whole = held == (reach - first_place(blk) if pad else reach)

        def by_pieces():
            def one(i, carry):
                attend(slot, i * piece, piece,
                       seen_of(blk, i * piece, piece, reach, whole), False)
                return carry
            lax.fori_loop(0, pl.cdiv(reach, piece), one, 0)

        def at_once(pages):
            return lambda: attend(
                slot, 0, pages, seen_of(blk, 0, pages, reach, whole), True)

        def multiply(ways):
            (pages, way), *rest = ways
            if rest:
                lax.cond(reach <= pages, way, lambda: multiply(rest))
            else:
                way()
        # (whole pieces only: a table's own length may not be)
        looped = [n for n in prefixes if n <= 2 * piece and not n % piece]
        multiply([(n, by_pieces) for n in looped[-1:]]
                 + [(n, at_once(n)) for n in prefixes if n not in looped])
        return carry

    def seen_of(blk, start, pages, reach, whole):
        """(1, positions) of `pages` pages of the lane's block from its page
        `start`: which of them the lane sees, of the `reach` pages the walk
        reaches in the block; `whole`: none of those is missing from the
        table."""
        at = start * page_size + lax.broadcasted_iota(
            jnp.int32, (1, pages * page_size), 1)
        pos = (blk * block_pages + first) * page_size + at
        if pad:
            pos = pos - pad * page_size
        seen = (pos < length) & (at < reach * page_size)
        if pad:
            seen = seen & (pos >= 0)
        if window:
            seen = seen & (pos >= length - window)

        def holes():     # 1 where the table has no page (as int32: a
            def one(p, out):                 # branch yields no mask)
                # (a page of a run is held where the run's first is)
                first = p - p % run if run > 1 else p
                if fixed:                   # (the head's, where it is)
                    first = jnp.where(blk * block_pages + p < head, p,
                                      first)
                return jnp.where((at // page_size == p)
                                 & ~page_at(me, blk, first)[1], 1, out)
            return lax.fori_loop(
                jnp.maximum(start, first_place(blk)) if pad else start,
                jnp.minimum(reach, start + pages), one, jnp.zeros_like(at))
        return seen & (lax.cond(whole, lambda: jnp.zeros_like(at), holes)
                       == 0)

    lax.fori_loop(0, n_blocks, body, 0)
    l = l_ref[..., :1]
    o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype)


def _softmax_update(q, k, v, seen, sm_scale: float, acc, m, l):
    """One block into the running softmax of `q` (rows, width): `k`
    (positions, width), `v` (positions, out), `seen` (1, positions); refs
    `acc` (rows, out), `m` and `l` (rows, 128) of these rows."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(seen, s, DEFAULT_MASK_VALUE)           # (rows, bk)
    m_prev = m[:, :1]                                    # (rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # (a block with nothing to see leaves m at the mask's value)
    prob = jnp.where(seen, jnp.exp(s - m_new), 0.0)
    l_new = alpha * l[:, :1] + jnp.sum(prob, axis=-1, keepdims=True)
    acc[:] = acc[:] * alpha + lax.dot_general(
        prob.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m[:] = jnp.broadcast_to(m_new, m.shape)
    l[:] = jnp.broadcast_to(l_new, l.shape)


def _paged_pallas_call(kernel, name: str, q, pools, layer,
                       page_tables, lengths, *, out_width: int, sems: tuple,
                       interpret: bool, **walk):
    """The `pallas_call` around `_walk_pages`: a grid over the lanes; the
    layer, lengths and flat tables by scalar prefetch; `q` (lanes, ...,
    rows, width) a lane a block; the pools left in HBM; scratch as
    `_walk_pages` takes it, the blocks of `walk_block_pages` pages by what
    a page of these pools weighs over the table's entries as the walk
    places them (`run_pad`). `kernel` gets the walk's sizes by keyword,
    and what else `walk` holds (a `window`, a `run`, a `fixed`)."""
    lanes, *rows = q.shape
    out = (*rows[:-1], out_width)
    stat = (*rows[:-1], 128)
    page_size, max_pages = pools[0].shape[2], page_tables.shape[1]
    block_pages = walk_block_pages(
        sum(page_size * pool.shape[3] * pool.dtype.itemsize
            for pool in pools), page_size,
        max_pages + run_pad(walk.get("fixed", 0), walk.get("run", 1)))

    def lane(b, *_):
        return (b,) + (0,) * len(rows)

    call = pl.pallas_call(
        functools.partial(kernel, page_size=page_size,
                          block_pages=block_pages, max_pages=max_pages,
                          **walk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(lanes,),
            in_specs=[pl.BlockSpec((1, *rows), lane)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec((1, *out), lane),
            scratch_shapes=[
                *(pltpu.VMEM((BLOCK_SLOTS, block_pages, page_size,
                              pool.shape[3]), pool.dtype)
                  for pool in pools),
                pltpu.SemaphoreType.DMA(sems),
                pltpu.VMEM(out, jnp.float32),            # acc
                pltpu.VMEM(stat, jnp.float32),           # running max
                pltpu.VMEM(stat, jnp.float32),           # running sum
                pltpu.SMEM((BLOCK_SLOTS,), jnp.int32),   # slots made finite
                pltpu.SMEM((2,), jnp.int32),        # lane to lane
            ]),
        out_shape=jax.ShapeDtypeStruct((lanes, *out), q.dtype),
        # the lanes in order, one core: a lane starts the next one's copies;
        # the buffers beside what a call is given when it asks for nothing
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=WALK_BUFFER_BYTES + (16 << 20)),
        interpret=interpret,
        name=name,
    )
    return call(jnp.asarray(layer, jnp.int32).reshape(1),
                lengths.astype(jnp.int32),
                page_tables.astype(jnp.int32).reshape(-1), q, *pools)


# ---------------------------------------------------------------- kernel
def _paged_decode_kernel(layer_ref, len_ref, pt_ref,       # scalars
                         q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, acc_ref, m_ref, l_ref,
                         finite_ref, hand_ref, *, sm_scale: float, **walk):
    kvh, _, hd = q_ref.shape[1:]

    def attend(slot, start, pages, seen, rolled):
        part, n = pl.ds(start, pages), pages * k_buf.shape[2]

        def head(h, lanes):                              # a head's lanes
            _softmax_update(
                q_ref[0, h],                             # (G, hd)
                k_buf[slot, part, :, lanes].reshape(n, hd),
                v_buf[slot, part, :, lanes].reshape(n, hd),
                seen, sm_scale, acc_ref.at[h], m_ref.at[h], l_ref.at[h])
        if rolled:      # a turn of the loop: as many heads as divide them
            turn = max(n for n in range(1, HEAD_UNROLL + 1) if not kvh % n)

            def some(i, carry):
                for h in range(turn):
                    h = i * turn + h
                    head(h, pl.ds(pl.multiple_of(h * hd, hd), hd))
                return carry
            lax.fori_loop(0, kvh // turn, some, 0)
        else:
            for h in range(kvh):
                head(h, slice(h * hd, (h + 1) * hd))

    _walk_pages(layer_ref, len_ref, pt_ref, o_ref, sems, acc_ref, m_ref,
                l_ref, finite_ref, hand_ref,
                ((k_hbm, k_buf, (0,)), (v_hbm, v_buf, (1,))), v_buf, attend,
                **walk)


def _paged_decode(q, k_pool, v_pool, layer, page_tables, lengths,
                  interpret: bool, mesh=None, run: int = 1, fixed: int = 0):
    n_heads, hd = q.shape[1:]
    page_size, kvh = k_pool.shape[2], k_pool.shape[3] // hd
    if n_heads % kvh:
        raise ValueError(
            f"num_heads ({n_heads}) must be a multiple of num_kv_heads "
            f"({kvh})")
    if not paged_decode_tiles(hd, page_size, k_pool.dtype,
                              k_pool.shape[3]):
        raise ValueError(
            f"the paged decode kernel does not tile {kvh} kv heads of {hd} "
            f"with {page_size}-position pages of {k_pool.dtype}")
    qg = _lane_queries(q, kvh)
    call = functools.partial(_paged_decode_call, interpret=interpret,
                             sm_scale=1.0 / math.sqrt(hd), run=run,
                             fixed=fixed)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if mesh is not None:
        # kv heads over tp, as models.decode.cache_sharding lays the pool
        tp = "tp" if mesh.shape.get("tp", 1) > 1 else None
        spec_q, spec_pool = P(None, tp, None, None), P(None, None, None, tp)
        out = shard_kernel(
            call, mesh, (spec_q, spec_pool, spec_pool, P(), P(), P()),
            spec_q)(qg, k_pool, v_pool, layer, page_tables, lengths)
    else:
        out = call(qg, k_pool, v_pool, layer, page_tables, lengths)
    return _own_parts(out, n_heads, hd)


# jitted: the decode step calls it once a layer with the same shapes, the
# layer's index an argument, so the kernel is traced and lowered once a
# program and not once a layer (24 of them cost a replica 8 s of set-up)
@functools.partial(jax.jit, static_argnames=("interpret", "sm_scale", "run",
                                             "fixed"))
def _paged_decode_call(qg, k_pool, v_pool, layer, page_tables, lengths,
                       interpret: bool, sm_scale: float, run: int = 1,
                       fixed: int = 0):
    """`sm_scale`: of the heads' own width, which a lane of several is
    not."""
    kernel = functools.partial(_paged_decode_kernel, sm_scale=sm_scale)
    return _paged_pallas_call(
        kernel, KERNEL_PAGED_DECODE, qg, (k_pool, v_pool), layer,
        page_tables, lengths, out_width=qg.shape[-1],
        sems=(BLOCK_SLOTS, 2), interpret=interpret, run=run, fixed=fixed)


def paged_decode_attention(q, k_pool, v_pool, layer, page_tables, lengths,
                           mesh=None, run: int = 1, fixed: int = 0):
    """Dispatching entry point: the compiled kernel when the target
    platform is a TPU and the shapes are ones it tiles
    (`paged_decode_tiles`), the gather + einsum reference elsewhere.
    Shapes as `paged_attention_reference`; `mesh`: the mesh of more than
    one device the pool is sharded over (`ops.dispatch.kernel_mesh`);
    `run`, `fixed`: the pages one copy of the kernel's walk brings behind
    the tables' first `fixed` entries, which the tables' owner vouches lie
    in such runs (`_walk_pages`; the gather reads any table)."""
    if uses_kernel(q.shape[-1], k_pool.shape[2], k_pool.dtype,
                   k_pool.shape[3]):
        return _paged_decode(q, k_pool, v_pool, layer, page_tables,
                             lengths, False, mesh, run, fixed)
    return paged_attention_reference(q, k_pool, v_pool, layer,
                                     page_tables, lengths)


def uses_kernel(head_dim: int, page_size: int, dtype,
                kv_dim: int = 0) -> bool:
    """What `paged_decode_attention` decides, for a caller that reports
    it: decided by what can be seen, the platform being traced for and
    the shapes, and by nothing else."""
    return on_tpu() and paged_decode_tiles(head_dim, page_size, dtype,
                                           kv_dim)


def paged_decode_attention_kernel(q, k_pool, v_pool, layer, page_tables,
                                  lengths, mesh=None, run: int = 1,
                                  fixed: int = 0):
    """Force the Pallas kernel path (interpreter off-TPU) — test hook."""
    return _paged_decode(q, k_pool, v_pool, layer, page_tables, lengths,
                         not on_tpu(), mesh, run, fixed)


# ------------------------------------------ a sliding window over a ring
KERNEL_PAGED_WINDOW_DECODE = "paged_window_decode_attn"


def ring_pages(window: int, page_size: int) -> int:
    """Pages a sequence keeps for a layer that sees its last `window`
    positions: the window's pages and one more, since its first and last
    page are both partly inside."""
    return -(-window // page_size) + 1


def ring_walk(length: int, window: int, page_size: int):
    """(live, read) positions of a lane `length` long in a window layer:
    what the window holds, and what the walk copies in (whole pages from
    the first the window reaches)."""
    first = max(length - window, 0) // page_size
    return (min(length, window),
            (-(-length // page_size) - first) * page_size)


def paged_window_attention_reference(q, k_pool, v_pool, layer, ring_tables,
                                     lengths, window: int):
    """Gather every ring entry, work out which position it holds, mask,
    softmax in float32.

    q (B, n_heads, hd); pools (layers, pages, page, kv * hd); ring_tables
    (B, ring) int32, -1 unassigned: logical page j of a lane lies at entry
    j mod ring, the newest such j winning; lengths (B,): a lane sees
    positions length - window .. length - 1. Returns (B, n_heads, hd) in
    q's dtype; a lane that sees nothing gets zeros."""
    page, ring = k_pool.shape[2], ring_tables.shape[1]
    # entry r holds the newest logical page j <= the lane's last with
    # j = r (mod ring)
    last = (lengths[:, None] - 1) // page                      # (B, 1)
    logical = last - (last - jnp.arange(ring)[None, :]) % ring  # (B, ring)
    pos = (jnp.repeat(logical, page, axis=1) * page
           + jnp.tile(jnp.arange(page), ring)[None, :])
    seen = ((pos >= 0) & (pos < lengths[:, None])
            & (pos >= lengths[:, None] - window))
    return _gathered_attention(q, k_pool, v_pool, layer, ring_tables, seen)


# jitted for the reason `_paged_decode_call` is: traced once a program
@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _paged_window_decode_call(q, k_pool, v_pool, layer, ring_tables,
                              lengths, window: int, interpret: bool):
    n_heads, hd = q.shape[1:]
    page_size, kvh = k_pool.shape[2], k_pool.shape[3] // hd
    if n_heads % kvh or not paged_decode_tiles(
            hd, page_size, k_pool.dtype, k_pool.shape[3]):
        raise ValueError(
            f"the paged window kernel does not take {n_heads} heads over "
            f"{kvh} kv heads of {hd} in {page_size}-position pages of "
            f"{k_pool.dtype}")
    if ring_tables.shape[1] < ring_pages(window, page_size):
        raise ValueError(
            f"a window of {window} needs a ring of "
            f"{ring_pages(window, page_size)} pages, the table has "
            f"{ring_tables.shape[1]}")
    kernel = functools.partial(_paged_decode_kernel,
                               sm_scale=1.0 / math.sqrt(hd))
    qg = _lane_queries(q, kvh)
    out = _paged_pallas_call(
        kernel, KERNEL_PAGED_WINDOW_DECODE, qg, (k_pool, v_pool), layer,
        ring_tables, lengths, out_width=qg.shape[-1],
        sems=(BLOCK_SLOTS, 2), interpret=interpret, window=window)
    return _own_parts(out, n_heads, hd)


def paged_window_decode_attention(q, k_pool, v_pool, layer, ring_tables,
                                  lengths, window: int):
    """Dispatching entry point of a window layer's decode attention: the
    compiled kernel on a TPU where the shapes tile, the gather + einsum
    reference elsewhere. Shapes as `paged_window_attention_reference`."""
    if uses_kernel(q.shape[-1], k_pool.shape[2], k_pool.dtype,
                   k_pool.shape[3]):
        return _paged_window_decode_call(q, k_pool, v_pool, layer,
                                         ring_tables, lengths, int(window),
                                         False)
    return paged_window_attention_reference(q, k_pool, v_pool, layer,
                                            ring_tables, lengths, window)


def paged_window_decode_attention_kernel(q, k_pool, v_pool, layer,
                                         ring_tables, lengths, window: int):
    """Force the Pallas kernel path (interpreter off-TPU) — test hook."""
    return _paged_window_decode_call(q, k_pool, v_pool, layer, ring_tables,
                                     lengths, int(window), not on_tpu())


# ------------------------------------------------- the latent (MLA) pool
#
# Multi-head latent attention in its absorbed form: the cache holds one
# row a position, `[c_kv (latent) | k_rope | zeros]`, padded to whole
# 128-lanes; a head's query is `[q_nope W_UK^T | q_rope | zeros]` of the
# same width, so a score is one dot product with the row, and the head's
# output is the probabilities times the rows' first `latent` numbers (the
# caller multiplies by W_UV afterwards). All heads share the rows: one
# matmul's rows are the heads.
KERNEL_MLA_PAGED_DECODE = "mla_paged_decode_attn"
# Bytes one copy of the latent walk should bring where the tables' owner
# lays a lane's pages in runs (`_walk_pages`' `run`): the scalar core needs
# some 50 ns to start a copy and await it, which a page of 20 KB (25 ns at
# 819 GB/s) does not cover, and a copy's bytes hide only part of it: a page
# read 49.8 / 33.4 / 27.2 / 27.4 ns at 1 / 2 / 4 / 8 pages a copy
# (`tools/bench_paged.py --run`; PERF.md section 6, PR 64), at its bytes
# from 80 KB on. Twice `ops.sparse_attention.RUN_COPY_BYTES`, which is
# sized by the index keys' page that walk brings beside this one.
MLA_RUN_COPY_BYTES = 64 << 10


def copy_run_pages(copy_bytes: int, page_bytes: int, *whole: int) -> int:
    """Pages a run holds so that one copy of it brings `copy_bytes` at
    `page_bytes` a page: the least power of two that does, cut to a
    divisor of each of `whole` (a table, a walk's block: a run lies in
    one)."""
    return math.gcd(1 << (-(-copy_bytes // page_bytes) - 1).bit_length(),
                    *whole)


def run_wholes(max_pages: int, fixed: int, block_of) -> tuple:
    """What a run of a table of `max_pages` entries has to divide
    (`copy_run_pages`' `whole`), `block_of(max_pages)` being the walk's
    block over such a table. Without a fixed class the table and its
    block. With `fixed` entries of that class in front the run is sized on
    the entries that grow and the table made whole runs of it
    (`run_table_pages`): no longer than the least power of two that holds
    them, and a divisor of the block of a table as long as can be, which
    a walk's blocks are or are whole runs of. The answer for a table and
    for the table `run_table_pages` makes of it is one."""
    if not fixed:
        return max_pages, block_of(max_pages)
    grow = max(max_pages - fixed, 1)
    return 1 << (grow - 1).bit_length(), block_of(1 << 30)


def mla_walk_run_pages(page_bytes: int, page_size: int,
                       max_pages: int, fixed: int = 0) -> int:
    """Pages one copy of the latent walk brings where a page of the pool
    is `page_bytes` (one layer's): `copy_run_pages` of `MLA_RUN_COPY_BYTES`
    in the table's `max_pages` and the walk's block (`run_wholes`)."""
    return copy_run_pages(
        MLA_RUN_COPY_BYTES, page_bytes, *run_wholes(
            max_pages, fixed,
            lambda n: walk_block_pages(page_bytes, page_size, n)))


# A page of one pool, one layer's, that a copy of the per-head walk brings
# alone: PR 64's rule (a copy is at its bytes from some 64-80 KB on,
# `MLA_RUN_COPY_BYTES`) read 16 KB a pool as runs of 4 and 8 KB as runs of
# 8; whether pages of 32 KB and more gain from runs of 2 nobody has
# measured (ROADMAP S11(a3)), and their classes keep a page a copy.
RUN_PAGE_BYTES = 32 << 10


def decode_walk_run_pages(pool_page_bytes: int, page_bytes: int,
                          page_size: int, max_pages: int,
                          fixed: int = 0) -> int:
    """Pages one copy of the per-head walk brings where a page of one pool
    is `pool_page_bytes` and of all the walk's pools `page_bytes` (one
    layer's): 1 from `RUN_PAGE_BYTES` a pool on, else `copy_run_pages` of
    `MLA_RUN_COPY_BYTES` in the table and the walk's block
    (`run_wholes`)."""
    if pool_page_bytes >= RUN_PAGE_BYTES:
        return 1
    return copy_run_pages(
        MLA_RUN_COPY_BYTES, pool_page_bytes, *run_wholes(
            max_pages, fixed,
            lambda n: walk_block_pages(page_bytes, page_size, n)))


def mla_paged_decode_tiles(width: int, latent: int, page_size: int,
                           dtype) -> bool:
    """Whether the latent kernel tiles these shapes: a row and its value
    part are whole 128-lanes, a page whole sublanes."""
    sublanes = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return (width % 128 == 0 and latent % 128 == 0
            and page_size % sublanes == 0)


def mla_paged_attention_reference(q, pool, layer, page_tables, lengths,
                                  latent: int, sm_scale: float):
    """Gather every table entry, mask, softmax in float32.

    q (B, heads, width); pool (layers, pages, page, width); page_tables
    (B, max_pages) int32, -1 unassigned; lengths (B,). Returns (B, heads,
    latent) in q's dtype; a lane that sees nothing gets zeros."""
    B = q.shape[0]
    num_pages, page, width = pool.shape[1:]
    span = page_tables.shape[1] * page
    pt = jnp.clip(page_tables, 0, num_pages - 1)
    rows = pool[layer][pt].reshape(B, span, width).astype(jnp.float32)
    mask = ((jnp.arange(span)[None, :] < lengths[:, None])
            & jnp.repeat(page_tables >= 0, page, axis=1))
    scores = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32),
                        rows) * sm_scale
    scores = jnp.where(mask[:, None, :], scores,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bsc->bhc", probs, rows[..., :latent])
    out = jnp.where(mask.any(axis=1)[:, None, None], out, 0.0)
    return out.astype(q.dtype)


def _mla_paged_decode_kernel(layer_ref, len_ref, pt_ref,       # scalars
                             q_ref, pool_hbm, o_ref,
                             buf, sems, acc_ref, m_ref, l_ref, finite_ref,
                             hand_ref, *, sm_scale: float, latent: int,
                             **walk):
    width = buf.shape[3]
    q = q_ref[0]                                         # (heads, width)

    def attend(slot, start, pages, seen, rolled):
        # keys; values in front
        rows = buf[slot, pl.ds(start, pages)].reshape(
            pages * buf.shape[2], width)
        _softmax_update(q, rows, rows[:, :latent], seen, sm_scale,
                        acc_ref, m_ref, l_ref)

    _walk_pages(layer_ref, len_ref, pt_ref, o_ref, sems, acc_ref, m_ref,
                l_ref, finite_ref, hand_ref, ((pool_hbm, buf, ()),), buf,
                attend, **walk)


# jitted for the reason `_paged_decode_call` is: traced once a program
@functools.partial(jax.jit, static_argnames=("latent", "sm_scale",
                                             "interpret", "run", "fixed"))
def _mla_paged_decode_call(q, pool, layer, page_tables, lengths,
                           latent: int, sm_scale: float, interpret: bool,
                           run: int = 1, fixed: int = 0):
    heads, width = q.shape[1:]
    page_size = pool.shape[2]
    if not mla_paged_decode_tiles(width, latent, page_size, pool.dtype):
        raise ValueError(
            f"the latent decode kernel does not tile rows of {width} "
            f"(value part {latent}) in {page_size}-position pages of "
            f"{pool.dtype}")
    # the heads are a matmul's rows: whole sublanes of them
    sublanes = 8 * max(1, 4 // jnp.dtype(q.dtype).itemsize)
    hp = -(-heads // sublanes) * sublanes
    qp = jnp.pad(q, ((0, 0), (0, hp - heads), (0, 0)))
    kernel = functools.partial(_mla_paged_decode_kernel, sm_scale=sm_scale,
                               latent=latent)
    out = _paged_pallas_call(
        kernel, KERNEL_MLA_PAGED_DECODE, qp, (pool,), layer, page_tables,
        lengths, out_width=latent, sems=(BLOCK_SLOTS,), interpret=interpret,
        run=run, fixed=fixed)
    return out[:, :heads]


def mla_uses_kernel(width: int, latent: int, page_size: int, dtype) -> bool:
    """What `mla_paged_decode_attention` decides, for a caller that
    reports it: the platform being traced for and the shapes."""
    return on_tpu() and mla_paged_decode_tiles(width, latent, page_size,
                                               dtype)


def mla_paged_decode_attention(q, pool, layer, page_tables, lengths,
                               latent: int, sm_scale: float, run: int = 1,
                               fixed: int = 0):
    """Dispatching entry point of the latent pool's decode attention: the
    compiled kernel on a TPU where the shapes tile, the gather + einsum
    reference elsewhere. Shapes as `mla_paged_attention_reference`; `run`,
    `fixed`: the pages one copy of the kernel's walk brings behind the
    tables' first `fixed` entries, which the tables' owner vouches lie in
    such runs (`_walk_pages`; the gather reads any table)."""
    if mla_uses_kernel(q.shape[-1], latent, pool.shape[2], pool.dtype):
        return _mla_paged_decode_call(q, pool, layer, page_tables, lengths,
                                      latent, float(sm_scale), False,
                                      run=run, fixed=fixed)
    return mla_paged_attention_reference(q, pool, layer, page_tables,
                                         lengths, latent, sm_scale)


def mla_paged_decode_attention_kernel(q, pool, layer, page_tables, lengths,
                                      latent: int, sm_scale: float,
                                      run: int = 1, fixed: int = 0):
    """Force the Pallas kernel path (interpreter off-TPU) — test hook."""
    return _mla_paged_decode_call(q, pool, layer, page_tables, lengths,
                                  latent, float(sm_scale), not on_tpu(),
                                  run=run, fixed=fixed)


# ------------------------------------- the latent pool in a ring (a window)
#
# A latent layer with a sliding window keeps a lane's last rows only, in a
# ring of the fixed class's pages, as a per-head window layer keeps its keys
# and values: the latent kernel's matmuls on the walk that begins at the
# first page the window reaches, the table wrapped, positions below the
# window masked. Its own name, so that a trace tells a ring's walks from
# the walks over a pool that grows.
KERNEL_MLA_PAGED_WINDOW_DECODE = "mla_paged_window_decode_attn"


def mla_paged_window_attention_reference(q, pool, layer, ring_tables,
                                         lengths, latent: int,
                                         sm_scale: float, window: int):
    """Gather every ring entry, work out which position it holds, mask,
    softmax in float32.

    q (B, heads, width); pool (layers, pages, page, width); ring_tables
    (B, ring) int32, -1 unassigned: logical page j of a lane lies at entry
    j mod ring, the newest such j winning; lengths (B,): a lane sees
    positions length - window .. length - 1. Returns (B, heads, latent) in
    q's dtype; a lane that sees nothing gets zeros."""
    B = q.shape[0]
    num_pages, page, width = pool.shape[1:]
    ring = ring_tables.shape[1]
    last = (lengths[:, None] - 1) // page                      # (B, 1)
    logical = last - (last - jnp.arange(ring)[None, :]) % ring  # (B, ring)
    pos = (jnp.repeat(logical, page, axis=1) * page
           + jnp.tile(jnp.arange(page), ring)[None, :])
    mask = ((pos >= 0) & (pos < lengths[:, None])
            & (pos >= lengths[:, None] - window)
            & jnp.repeat(ring_tables >= 0, page, axis=1))
    pt = jnp.clip(ring_tables, 0, num_pages - 1)
    rows = pool[layer][pt].reshape(B, ring * page, width).astype(
        jnp.float32)
    scores = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32),
                        rows) * sm_scale
    scores = jnp.where(mask[:, None, :], scores,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bsc->bhc", probs, rows[..., :latent])
    out = jnp.where(mask.any(axis=1)[:, None, None], out, 0.0)
    return out.astype(q.dtype)


# jitted for the reason `_paged_decode_call` is: traced once a program
@functools.partial(jax.jit, static_argnames=("latent", "sm_scale", "window",
                                             "interpret"))
def _mla_paged_window_decode_call(q, pool, layer, ring_tables, lengths,
                                  latent: int, sm_scale: float, window: int,
                                  interpret: bool):
    heads, width = q.shape[1:]
    page_size = pool.shape[2]
    if not mla_paged_decode_tiles(width, latent, page_size, pool.dtype):
        raise ValueError(
            f"the latent window kernel does not tile rows of {width} "
            f"(value part {latent}) in {page_size}-position pages of "
            f"{pool.dtype}")
    if ring_tables.shape[1] < ring_pages(window, page_size):
        raise ValueError(
            f"a window of {window} needs a ring of "
            f"{ring_pages(window, page_size)} pages, the table has "
            f"{ring_tables.shape[1]}")
    # the heads are a matmul's rows: whole sublanes of them
    sublanes = 8 * max(1, 4 // jnp.dtype(q.dtype).itemsize)
    hp = -(-heads // sublanes) * sublanes
    qp = jnp.pad(q, ((0, 0), (0, hp - heads), (0, 0)))
    kernel = functools.partial(_mla_paged_decode_kernel, sm_scale=sm_scale,
                               latent=latent)
    out = _paged_pallas_call(
        kernel, KERNEL_MLA_PAGED_WINDOW_DECODE, qp, (pool,), layer,
        ring_tables, lengths, out_width=latent, sems=(BLOCK_SLOTS,),
        interpret=interpret, window=window)
    return out[:, :heads]


def mla_paged_window_decode_attention(q, pool, layer, ring_tables, lengths,
                                      latent: int, sm_scale: float,
                                      window: int):
    """Dispatching entry point of a window layer's latent decode attention:
    the compiled kernel on a TPU where the shapes tile, the gather + einsum
    reference elsewhere. Shapes as `mla_paged_window_attention_reference`."""
    if mla_uses_kernel(q.shape[-1], latent, pool.shape[2], pool.dtype):
        return _mla_paged_window_decode_call(
            q, pool, layer, ring_tables, lengths, latent, float(sm_scale),
            int(window), False)
    return mla_paged_window_attention_reference(
        q, pool, layer, ring_tables, lengths, latent, sm_scale, window)


def mla_paged_window_decode_attention_kernel(q, pool, layer, ring_tables,
                                             lengths, latent: int,
                                             sm_scale: float, window: int):
    """Force the Pallas kernel path (interpreter off-TPU) — test hook."""
    return _mla_paged_window_decode_call(
        q, pool, layer, ring_tables, lengths, latent, float(sm_scale),
        int(window), not on_tpu())
