"""Learned sparse attention over a latent cache (the DeepSeek sparse
attention of `glm_moe_dsa`): a small second attention, the indexer, scores
every position a query may see, and the main attention's softmax runs over
the `topk` best of them alone.

With `q^I_t` (heads, width) the indexer's queries of position t, `k^I_s`
(width,) the one index key a position has for all heads, and `w_t` (heads,)
the query's head weights (scaled by the caller):

    I[t, s] = sum_j w[t, j] * relu(q^I[t, j] . k^I[s])      s <= t, float32
    S_t     = the min(topk, t + 1) positions s <= t of largest I[t, s]

**A decode step** (`choose_paged`, `attend_chosen`): the index keys lie in
a pool of their own, `(layers, pages, page_size, width)`, under the page ids
of the latent pool. On a TPU both halves are kernels that walk the pages a
lane holds, the next block's copies behind the block being multiplied:
`dsa_paged_index` scores every live position (4,096 a block), the lane's
`topk`-th largest score is found by bisection on the scores' bits
(`keep_topk`: no sort, and no list of positions either), and
`dsa_paged_attend` is the absorbed latent attention (`ops.paged_attention`'s
row layout: `[c_kv | k_rope | zeros]`, key all of it, value its first
`latent` numbers) over every live row with the chosen ones kept: a page of
16 rows costs one copy as a single row of 1,280 bytes would, so up to some
32k positions a lane reading all and keeping 2,048 is cheaper than
gathering 2,048. Elsewhere the scores are a gather of the table's pages
(`index_scores_paged`), the choice `lax.top_k` (`select_topk`: ties to the
lower position) and the attention a gather of the chosen rows
(`mla_selected_attention`). All of it is on the device: the host builds no
list.

**A prefill** (`prefill_keep_mask`, `masked_flash_attention`): the scores
of a block of `SELECT_ROWS` queries against every key are one tile kernel
(`dsa_index_scores`: the heads' ReLU'd products summed in VMEM, so the
`heads x s x s` products never exist), the `topk`-th largest score of each
row is found by bisection on the scores' bits (32 counting passes over the
block: no sort), and the keys at or above it, causal, are the row's set,
kept as int8. A tie at the threshold keeps every tied key (a prefill's
and a step's kernels alike) where `top_k` would keep the lower positions:
equal float32 sums of 32 products. The main attention is the flash forward
with that mask read tile by tile beside the keys (`dsa_flash_fwd`); blocks
above the diagonal are neither copied nor multiplied.

Each piece has a plain form (the off-TPU path and the tests' ground
truth); tests reach the kernels through the Pallas interpreter
(`interpret=True`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import DEFAULT_MASK_VALUE
from ray_tpu.ops.dispatch import on_tpu
from ray_tpu.ops.paged_attention import (_softmax_update, copy_run_pages,
                                         run_pad, run_wholes)

# The kernels' names on the device's clock (see attention.KERNEL_FWD).
KERNEL_INDEX_SCORES = "dsa_index_scores"
KERNEL_FLASH_FWD = "dsa_flash_fwd"
KERNEL_PAGED_INDEX = "dsa_paged_index"
KERNEL_PAGED_ATTEND = "dsa_paged_attend"

# Queries whose scores exist together while their sets are chosen: a block
# of (SELECT_ROWS, s) float32, 134 MB at 16,384 keys
SELECT_ROWS = 2048
# tiles of the index-score kernel (queries, keys) up to INDEX_HEADS index
# heads; the kernel unrolls a tile's heads, and at 64 of them a tile of
# 512 queries asked for 100.5 MB of the 96 its fast memory has (compiled
# for v5e without a chip, PR 65): past INDEX_HEADS the query block shrinks
# in their ratio
INDEX_BLOCKS = (512, 1024)
INDEX_HEADS = 32
# blocks of the masked flash forward (queries, keys): the latent classes'
# (`models.latent.PREFILL_BLOCKS`) in queries, half in keys, so that the
# mask's tile fits beside the keys and values of 256
FLASH_BLOCKS = (1024, 512)
FLASH_VMEM_BYTES = 48 << 20


# ---------------------------------------------------------------- decode
def _live(page_tables, lengths, page: int):
    """bool (B, max_pages * page): the positions a lane holds."""
    span = page_tables.shape[1] * page
    return ((jnp.arange(span)[None, :] < lengths[:, None])
            & jnp.repeat(page_tables >= 0, page, axis=1))


def index_scores_paged(q_idx, w, idx_pool, layer: int, page_tables,
                       lengths):
    """The indexer's scores of one query a lane against the positions the
    lane holds.

    q_idx (B, heads, width); w (B, heads) float32; idx_pool (layers, pages,
    page, width); page_tables (B, max_pages) int32, -1 unassigned; lengths
    (B,). Returns (B, max_pages * page) float32, -inf at a position the
    lane does not hold."""
    B = q_idx.shape[0]
    num_pages, page, width = idx_pool.shape[1:]
    span = page_tables.shape[1] * page
    pt = jnp.clip(page_tables, 0, num_pages - 1)
    keys = idx_pool[layer, pt].reshape(B, span, width)
    prod = jnp.einsum("bhd,bsd->bhs", q_idx.astype(keys.dtype), keys,
                      preferred_element_type=jnp.float32)
    scores = jnp.einsum("bh,bhs->bs", w.astype(jnp.float32),
                        jax.nn.relu(prod),
                        precision=lax.Precision.HIGHEST)
    return jnp.where(_live(page_tables, lengths, page), scores, -jnp.inf)


def select_topk(scores, topk: int):
    """(positions (B, k) int32, chosen (B, k) bool) of the `k = min(topk,
    span)` largest scores a lane, ties to the lower position; `chosen` is
    False where the lane holds fewer than k positions."""
    vals, idx = lax.top_k(scores, min(topk, scores.shape[-1]))
    return idx.astype(jnp.int32), vals > -jnp.inf


def mla_selected_attention(q, pool, layer: int, page_tables, positions,
                           chosen, latent: int, sm_scale: float):
    """The absorbed latent attention over chosen positions alone.

    q (B, heads, width) `[q_lat | q_rope | zeros]`; pool (layers, pages,
    page, width); positions (B, k) int32 with `chosen` (B, k) bool. Returns
    (B, heads, latent) in q's dtype; a lane with nothing chosen gets
    zeros."""
    page = pool.shape[2]
    entry = jnp.take_along_axis(page_tables, positions // page, axis=1)
    rows = pool[layer, jnp.clip(entry, 0, pool.shape[1] - 1),
                positions % page]                       # (B, k, width)
    chosen = chosen & (entry >= 0)
    scores = jnp.einsum("bhw,bkw->bhk", q.astype(rows.dtype), rows,
                        preferred_element_type=jnp.float32) * sm_scale
    scores = jnp.where(chosen[:, None, :], scores, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhk,bkc->bhc", probs.astype(rows.dtype),
                     rows[..., :latent],
                     preferred_element_type=jnp.float32)
    out = jnp.where(chosen.any(axis=1)[:, None, None], out, 0.0)
    return out.astype(q.dtype)


# ------------------------------------------------- the step's two kernels
#
# Both walk a lane's live pages in table order, `pages` at a time, the next
# block's copies started behind the block being multiplied (a copy a page:
# a page is contiguous in the pool). Neither walks what a lane does not
# hold, which is what the gathers above cannot help doing: they read every
# table entry (the index scores) or a row a copy (the chosen rows: 1,280
# bytes each, where a page of 16 rows costs one copy too, so that up to
# some 32k positions a lane reading every live row and masking is the
# cheaper way to read 2,048 of them).
#
# A copy costs the scalar core some 40-58 ns to start whatever it brings
# (PERF.md section 6, PR 61 and PR 62), which a page of 4 KB of index keys
# (5 ns of bytes) or of 20 KB of latent rows (25 ns) does not cover. Where
# the allocator hands a sequence its pages in aligned runs of `run`
# consecutive ids (`serve/llm/kv_cache.py`; `walk_run_pages` says how
# long), a walk is told so and brings a run a copy: entry `k * run` of a
# table names the first page of `run` that lie behind one another in the
# pool, all the sequence's own, those no position has reached yet too.
INDEX_WALK_PAGES = 256          # 4,096 positions of index keys a block
ATTEND_WALK_PAGES = 64          # 1,024 latent rows a block
# Bytes of the smaller pool that one copy of a walk should bring, so that
# the bytes and not the descriptor are what a copy costs
RUN_COPY_BYTES = 32 << 10


def walk_run_pages(page_bytes: int, max_pages: int, fixed: int = 0) -> int:
    """Pages a run holds where a page of the smaller pool walked is
    `page_bytes` (one layer's): as many as make a copy of `RUN_COPY_BYTES`,
    cut to a divisor of the table's `max_pages` and of both walks' blocks
    (a run lies in one block; `ops.paged_attention.run_wholes` for tables
    with `fixed` entries of the fixed class in front)."""
    return copy_run_pages(
        RUN_COPY_BYTES, page_bytes, *run_wholes(
            max_pages, fixed,
            lambda n: math.gcd(min(INDEX_WALK_PAGES, n),
                               min(ATTEND_WALK_PAGES, n))))


def _walk_blocks(layer_ref, len_ref, pt_ref, pool_hbm, buf, sems, block,
                 *, page_size: int, block_pages: int, max_pages: int,
                 run: int, fixed: int = 0):
    """`block(blk, slot)` on every block of `block_pages` pages the grid's
    lane holds, its pages copied into `buf[slot]` (`slots`, block_pages,
    page_size, width), the next block's on their way meanwhile: a copy a
    page, or one a run of `run` pages that holds a live page (the table's
    entry `k * run` names the run's first). With `fixed` the tables' first
    `fixed` entries are copied a page each and the runs open at entry
    `fixed`: entry `e` lies at place `e + run_pad(fixed, run)` of the walk
    (`ops.paged_attention._walk_pages`), and the first block's leading
    places hold what the buffer held."""
    b = pl.program_id(0)
    layer = layer_ref[0]
    pad = run_pad(fixed, run)
    pages = pl.cdiv(len_ref[b], page_size)
    if pad:
        pages = jnp.where(pages > 0, pages + pad, 0)
    n_blocks = pl.cdiv(pages, block_pages)

    def each_copy(blk, slot, act):
        def loop(step, first, end):
            """The copies of `step` pages each from place `first` (None:
            the block's first) up to `end`."""
            def place(k):
                return k * step if first is None else first + k * step

            def one(k, carry):
                entry = b * max_pages + blk * block_pages + place(k)
                page = jnp.maximum(pt_ref[entry - pad if pad else entry], 0)
                act(pltpu.make_async_copy(
                    pool_hbm.at[layer,
                                pl.ds(pl.multiple_of(page, step), step)],
                    buf.at[slot,
                           pl.ds(pl.multiple_of(place(k), step), step)],
                    sems.at[slot]))
                return carry
            lax.fori_loop(0, pl.cdiv(end if first is None else end - first,
                                     step), one, 0)

        reach = jnp.minimum(pages - blk * block_pages, block_pages)
        if not fixed:
            return loop(run, None, reach)
        at = blk * block_pages
        runs = jnp.clip(fixed + pad - at, 0, block_pages)
        loop(1, jnp.clip(pad - at, 0, block_pages), jnp.minimum(runs, reach))
        loop(run, runs, reach)

    @pl.when(n_blocks > 0)
    def _():
        each_copy(0, 0, lambda copy: copy.start())

    def body(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            each_copy(blk + 1, 1 - slot, lambda copy: copy.start())
        each_copy(blk, slot, lambda copy: copy.wait())
        block(blk, slot)
        return carry
    lax.fori_loop(0, n_blocks, body, 0)


def _paged_index_kernel(layer_ref, len_ref, pt_ref,             # scalars
                        q_ref, w_ref, pool_hbm, o_ref, buf, sems, **walk):
    positions = buf.shape[1] * buf.shape[2]
    q, w = q_ref[0], w_ref[0]                   # (heads, width), (heads, 1)
    o_ref[...] = jnp.full_like(o_ref, -jnp.inf)

    def block(blk, slot):
        keys = buf[slot].reshape(positions, buf.shape[3])
        prod = lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
        at = pl.multiple_of(blk * positions, positions)
        o_ref[0, :, pl.ds(at, positions)] = jnp.sum(
            jnp.maximum(prod, 0.0) * w, axis=0, keepdims=True)

    _walk_blocks(layer_ref, len_ref, pt_ref, pool_hbm, buf, sems, block,
                 **walk)


def _walk_layout(max_pages: int, walk_pages: int, run: int, fixed: int):
    """(fixed as the walk takes it, the first table entry's place, a
    block's pages, the walk's blocks) of a walk in blocks of up to
    `walk_pages` over tables of `max_pages` entries. Tables without a
    fixed class, and those walked a page a copy, are whole blocks, each
    whole runs. Tables with `fixed` entries of that class in front and
    whole runs behind them (`ops.paged_attention.run_table_pages`) are
    walked from place `run_pad(fixed, run)` on in blocks of `walk_pages`,
    the last one past the table's end where it must be: the caller cuts
    the walk's span to the table's."""
    block_pages = min(walk_pages, max_pages)
    if run == 1 and not max_pages % block_pages:
        fixed = 0
    if not fixed:
        if max_pages % block_pages or block_pages % run:
            raise ValueError(
                f"tables of {max_pages} pages are not whole blocks of "
                f"{block_pages} in whole runs of {run}")
        return 0, 0, block_pages, max_pages // block_pages
    if walk_pages % run or (max_pages - fixed) % run:
        raise ValueError(
            f"tables of {max_pages} pages are not {fixed} fixed and whole "
            f"runs of {run} in blocks of {walk_pages}")
    pad = run_pad(fixed, run)
    return fixed, pad, walk_pages, -(-(max_pages + pad) // walk_pages)


@functools.partial(jax.jit, static_argnames=("interpret", "run", "fixed"))
def _paged_index_call(q_idx, w, idx_pool, layer, page_tables, lengths,
                      interpret: bool, run: int = 1, fixed: int = 0):
    lanes, heads, width = q_idx.shape
    page_size, max_pages = idx_pool.shape[2], page_tables.shape[1]
    fixed, pad, block_pages, blocks = _walk_layout(
        max_pages, INDEX_WALK_PAGES, run, fixed)
    span = blocks * block_pages * page_size

    def lane(b, *_):
        return b, 0, 0

    call = pl.pallas_call(
        functools.partial(_paged_index_kernel, page_size=page_size,
                          block_pages=block_pages, max_pages=max_pages,
                          run=run, fixed=fixed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(lanes,),
            in_specs=[pl.BlockSpec((1, heads, width), lane),
                      pl.BlockSpec((1, heads, 1), lane),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 1, span), lane),
            scratch_shapes=[
                pltpu.VMEM((2, block_pages, page_size, width),
                           idx_pool.dtype),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((lanes, 1, span), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL_PAGED_INDEX,
    )
    scores = call(jnp.asarray(layer, jnp.int32).reshape(1),
                  lengths.astype(jnp.int32),
                  page_tables.astype(jnp.int32).reshape(-1),
                  q_idx.astype(idx_pool.dtype),
                  w.astype(jnp.float32)[..., None], idx_pool)[:, 0]
    if fixed:                   # the walk's places -> the table's entries
        scores = lax.slice_in_dim(scores, pad * page_size,
                                  (pad + max_pages) * page_size, axis=1)
    return jnp.where(_live(page_tables, lengths, page_size), scores,
                     -jnp.inf)


def _paged_attend_kernel(layer_ref, len_ref, pt_ref,            # scalars
                         q_ref, keep_ref, pool_hbm, o_ref,
                         buf, sems, acc_ref, m_ref, l_ref, *,
                         sm_scale: float, latent: int, **walk):
    positions = buf.shape[1] * buf.shape[2]
    width = buf.shape[3]
    q = q_ref[0]                                        # (heads, width)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[:] = jnp.zeros_like(l_ref)

    # a probability of 0 does not neutralise what a buffer held before its
    # first copy (0 * NaN): numbers once a call, the pool's own ever after
    @pl.when(pl.program_id(0) == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)

    def block(blk, slot):
        rows = buf[slot].reshape(positions, width)
        at = pl.multiple_of(blk * positions, positions)
        seen = keep_ref[0, :, pl.ds(at, positions)] > 0.5
        _softmax_update(q, rows, rows[:, :latent], seen, sm_scale,
                        acc_ref, m_ref, l_ref)

    _walk_blocks(layer_ref, len_ref, pt_ref, pool_hbm, buf, sems, block,
                 **walk)
    l = l_ref[:, :1]
    o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("latent", "sm_scale",
                                             "interpret", "run", "fixed"))
def _paged_attend_call(q, pool, layer, page_tables, lengths, keep,
                       latent: int, sm_scale: float, interpret: bool,
                       run: int = 1, fixed: int = 0):
    lanes, heads, width = q.shape
    page_size, max_pages = pool.shape[2], page_tables.shape[1]
    fixed, pad, block_pages, blocks = _walk_layout(
        max_pages, ATTEND_WALK_PAGES, run, fixed)
    span = blocks * block_pages * page_size
    if fixed:                   # the table's entries -> the walk's places
        keep = jnp.pad(keep, ((0, 0), (
            pad * page_size, span - (pad + max_pages) * page_size)))
    sublanes = 8 * max(1, 4 // jnp.dtype(q.dtype).itemsize)
    hp = -(-heads // sublanes) * sublanes
    qp = jnp.pad(q, ((0, 0), (0, hp - heads), (0, 0)))

    def lane(b, *_):
        return b, 0, 0

    call = pl.pallas_call(
        functools.partial(_paged_attend_kernel, sm_scale=sm_scale,
                          latent=latent, page_size=page_size,
                          block_pages=block_pages, max_pages=max_pages,
                          run=run, fixed=fixed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(lanes,),
            in_specs=[pl.BlockSpec((1, hp, width), lane),
                      pl.BlockSpec((1, 1, span), lane),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, hp, latent), lane),
            scratch_shapes=[
                pltpu.VMEM((2, block_pages, page_size, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((hp, latent), jnp.float32),
                pltpu.VMEM((hp, 128), jnp.float32),
                pltpu.VMEM((hp, 128), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((lanes, hp, latent), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL_PAGED_ATTEND,
    )
    out = call(jnp.asarray(layer, jnp.int32).reshape(1),
               lengths.astype(jnp.int32),
               page_tables.astype(jnp.int32).reshape(-1), qp,
               keep.astype(jnp.float32)[:, None, :], pool)
    return out[:, :heads]


def step_kernels_tile(index_width: int, row_width: int, latent: int,
                      page_size: int, max_pages: int, dtype,
                      fixed: int = 0) -> bool:
    """Whether the step's two kernels tile these shapes: rows of whole
    128-lanes, pages of whole sublanes, tables of whole blocks, or with
    `fixed` entries of the fixed class in front, which are walked in the
    walks' own blocks whatever their length (`_walk_layout`)."""
    sublanes = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    if not (index_width % 128 == 0 and row_width % 128 == 0
            and latent % 128 == 0 and page_size % sublanes == 0):
        return False
    if fixed:
        return (ATTEND_WALK_PAGES * page_size) % 128 == 0
    return (max_pages % min(INDEX_WALK_PAGES, max_pages) == 0
            and max_pages % min(ATTEND_WALK_PAGES, max_pages) == 0
            and (min(ATTEND_WALK_PAGES, max_pages) * page_size) % 128 == 0)


def keep_topk(scores, topk: int):
    """bool (B, span): the positions whose score is at or above the row's
    `topk`-th largest (every position that holds a score, -inf being none,
    where there are no more than `topk`): by bisection on the scores'
    bits, no sort. A tie at the threshold keeps every tied position."""
    keys = _ordered_bits(scores)
    kth = _kth_largest_key(keys, min(topk, scores.shape[-1]))
    return (scores > -jnp.inf) & (keys >= kth[:, None])


def step_uses_kernels(index_width: int, row_width: int, latent: int,
                      page_size: int, max_pages: int, dtype,
                      fixed: int = 0) -> bool:
    """What `choose_paged` and `attend_chosen` are told by a caller that
    lets the platform being traced for and the shapes decide."""
    return on_tpu() and step_kernels_tile(
        index_width, row_width, latent, page_size, max_pages, dtype, fixed)


def choose_paged(q_idx, w, idx_pool, layer: int, page_tables, lengths,
                 topk: int, kernel: bool, interpret: bool = False,
                 run: int = 1, fixed: int = 0):
    """A decode step's indexer of one layer: the scores of every position
    a lane holds and the `topk` best of them. Shapes as
    `index_scores_paged`. Returns (the choice as `attend_chosen` takes it,
    positions chosen a lane (B,) int32). `kernel`: the walk over the live
    index pages as a kernel and the choice a mask over the table's span
    (`keep_topk`), else the gather and `select_topk`'s positions. `run`,
    `fixed`: the pages one copy of the kernel's walk brings behind the
    tables' first `fixed` entries, which the tables' owner vouches lie in
    such runs (`_walk_blocks`)."""
    if kernel:
        keep = keep_topk(_paged_index_call(
            q_idx, w, idx_pool, layer, page_tables, lengths, interpret,
            run=run, fixed=fixed), topk)
        return keep, jnp.sum(keep, axis=1).astype(jnp.int32)
    positions, chosen = select_topk(index_scores_paged(
        q_idx, w, idx_pool, layer, page_tables, lengths), topk)
    return (positions, chosen), jnp.sum(chosen, axis=1).astype(jnp.int32)


def attend_chosen(q, pool, layer: int, page_tables, lengths, choice,
                  latent: int, sm_scale: float, kernel: bool,
                  interpret: bool = False, run: int = 1, fixed: int = 0):
    """The absorbed attention over what `choose_paged` chose (with the same
    `kernel`, `run` and `fixed`): every live row read and the chosen kept,
    as a kernel, or the chosen rows gathered. Returns (B, heads, latent)
    in q's dtype."""
    if kernel:
        return _paged_attend_call(q, pool, layer, page_tables, lengths,
                                  choice, latent, float(sm_scale), interpret,
                                  run=run, fixed=fixed)
    return mla_selected_attention(q, pool, layer, page_tables, *choice,
                                  latent, sm_scale)


# --------------------------------------------------------------- prefill
def index_scores_reference(q_idx, w, k_idx):
    """I (rows, s) float32 of queries q_idx (rows, heads, width), w (rows,
    heads) against keys k_idx (s, width); no mask."""
    prod = jnp.einsum("thd,sd->ths", q_idx, k_idx.astype(q_idx.dtype),
                      preferred_element_type=jnp.float32)
    return jnp.einsum("th,ths->ts", w.astype(jnp.float32),
                      jax.nn.relu(prod), precision=lax.Precision.HIGHEST)


def _index_scores_kernel(first_ref, q_ref, w_ref, k_ref, o_ref, *,
                         heads: int, block_q: int, block_k: int):
    i, j = pl.program_id(0), pl.program_id(1)

    # a tile wholly above the diagonal is nobody's: left as it lies, the
    # caller's causal mask covers it
    @pl.when(j * block_k <= first_ref[0] + i * block_q + block_q - 1)
    def _():
        k = k_ref[...]
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for h in range(heads):
            prod = lax.dot_general(q_ref[h], k, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(prod, 0.0) * w_ref[:, h:h + 1]
        o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def _index_scores_call(q_idx, w, k_idx, first_row, interpret: bool):
    """`index_scores_reference` of queries `first_row ..` as the tile
    kernel; what lies wholly above the diagonal is not computed and holds
    anything."""
    rows, heads, width = q_idx.shape
    s = k_idx.shape[0]
    block_q = min(INDEX_BLOCKS[0] * INDEX_HEADS // max(heads, INDEX_HEADS),
                  rows)
    block_k = min(INDEX_BLOCKS[1], s)
    if rows % block_q or s % block_k:
        raise ValueError(f"{rows} queries and {s} keys are not whole "
                         f"tiles of {block_q} x {block_k}")

    def keys(i, j, first):      # none behind the diagonal's is copied
        return jnp.minimum(
            j, (first[0] + i * block_q + block_q - 1) // block_k), 0

    call = pl.pallas_call(
        functools.partial(_index_scores_kernel, heads=heads,
                          block_q=block_q, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // block_q, s // block_k),
            in_specs=[pl.BlockSpec((heads, block_q, width),
                                   lambda i, j, first: (0, i, 0)),
                      pl.BlockSpec((block_q, heads),
                                   lambda i, j, first: (i, 0)),
                      pl.BlockSpec((block_k, width), keys)],
            out_specs=pl.BlockSpec((block_q, block_k),
                                   lambda i, j, first: (i, j))),
        out_shape=jax.ShapeDtypeStruct((rows, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_INDEX_SCORES,
    )
    return call(jnp.asarray(first_row, jnp.int32).reshape(1),
                q_idx.transpose(1, 0, 2), w.astype(jnp.float32),
                k_idx.astype(q_idx.dtype))


def _kth_largest_key(keys, k: int):
    """Each row's k-th largest of uint32 `keys` (rows, s), by bisection on
    the bits from the top: 32 passes that count, no sort."""
    def bit(i, found):
        trial = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= trial[:, None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, trial, found)
    return lax.fori_loop(0, 32, bit,
                         jnp.zeros(keys.shape[:1], jnp.uint32))


def _ordered_bits(x):
    """float32 -> uint32 that orders as the floats do (-inf lowest)."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def keep_rows(scores, first_row, topk: int):
    """int8 (rows, s): 1 where key s is in the set of query `first_row + r`:
    causal, and among the `topk` largest of the row's causal scores
    (`keep_topk`; what lies above the diagonal may hold anything)."""
    rows, s = scores.shape
    causal = (jnp.arange(s)[None, :]
              <= first_row + jnp.arange(rows)[:, None])
    return keep_topk(jnp.where(causal, scores, -jnp.inf), topk).astype(
        jnp.int8)


def prefill_keep_mask(q_idx, w, k_idx, topk: int, kernel=None,
                      interpret: bool = False):
    """int8 (s, s) of one sequence: [t, s'] 1 where key s' is in query t's
    set. q_idx (s, heads, width); w (s, heads) float32; k_idx (s, width).
    `kernel`: the tile kernel (None: on a TPU where the shapes tile)."""
    s = k_idx.shape[0]
    rows = min(SELECT_ROWS, s)
    if kernel is None:
        kernel = on_tpu() and s % min(INDEX_BLOCKS[1], s) == 0 \
            and rows % min(INDEX_BLOCKS[0], rows) == 0
    if s % rows:
        raise ValueError(f"{s} positions are not whole blocks of {rows}")

    def block(b):
        first = b * rows
        qb = lax.dynamic_slice_in_dim(q_idx, first, rows)
        wb = lax.dynamic_slice_in_dim(w, first, rows)
        scores = (_index_scores_call(qb, wb, k_idx, first, interpret)
                  if kernel
                  else index_scores_reference(qb, wb, k_idx))
        return keep_rows(scores, first, topk)

    return lax.map(block, jnp.arange(s // rows)).reshape(s, s)


def masked_attention_reference(q, k, v, keep, sm_scale: float):
    """q (h, s, d), k (h, s, d), v (h, s, dv), keep (s, s): softmax over
    the kept keys of each query, in float32."""
    logits = jnp.einsum("hqd,hkd->hqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    logits = jnp.where(keep[None] != 0, logits, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", probs.astype(v.dtype), v)


def _masked_flash_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, sm_scale: float,
                         block_q: int, block_k: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(j * block_k <= i * block_q + block_q - 1)
    def _():
        v = v_ref[0]
        s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        kept = keep_ref[...] != 0
        s = jnp.where(kept, s, DEFAULT_MASK_VALUE)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # (a row that keeps nothing of this block leaves m at the mask)
        p = jnp.where(kept, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _masked_flash_call(q, k, v, keep, sm_scale: float, interpret: bool):
    h, s, d = q.shape
    dv = v.shape[-1]
    block_q, block_k = min(FLASH_BLOCKS[0], s), min(FLASH_BLOCKS[1], s)
    if s % block_q or s % block_k:
        raise ValueError(f"{s} positions are not whole blocks of "
                         f"{block_q} x {block_k}")

    def last_key_block(i):      # the diagonal's: none behind it is copied
        return (i * block_q + block_q - 1) // block_k

    def keys(h_, i, j):
        return h_, jnp.minimum(j, last_key_block(i)), 0

    call = pl.pallas_call(
        functools.partial(_masked_flash_kernel, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k),
        grid=(h, s // block_q, s // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h_, i, j: (h_, i, 0)),
            pl.BlockSpec((1, block_k, d), keys),
            pl.BlockSpec((1, block_k, dv), keys),
            pl.BlockSpec((block_q, block_k), lambda h_, i, j: (
                i, jnp.minimum(j, last_key_block(i))))],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda h_, i, j: (h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((h, s, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dv), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=FLASH_VMEM_BYTES),
        interpret=interpret,
        name=KERNEL_FLASH_FWD,
    )
    return call(q, k, v, keep)


def masked_flash_tiles(s: int, d: int, dv: int) -> bool:
    """Whether the masked flash forward tiles these shapes: whole blocks
    of positions, values of whole 128-lanes, keys of whole halves (a key
    of 192 is a block's whole last dimension, as the flash forward takes
    it)."""
    return (s % min(FLASH_BLOCKS[0], s) == 0
            and s % min(FLASH_BLOCKS[1], s) == 0 and s % 128 == 0
            and d % 64 == 0 and dv % 128 == 0)


def masked_flash_attention(q, k, v, keep, sm_scale: float):
    """Dispatching entry point of a prefill's attention over each query's
    own set: the kernel on a TPU where the shapes tile, the plain form
    elsewhere. Shapes as `masked_attention_reference`."""
    if on_tpu() and masked_flash_tiles(q.shape[1], q.shape[2], v.shape[2]):
        return _masked_flash_call(q, k, v, keep, float(sm_scale), False)
    return masked_attention_reference(q, k, v, keep, sm_scale)


def masked_flash_attention_kernel(q, k, v, keep, sm_scale: float):
    """Force the Pallas kernel path (interpreter off-TPU) — test hook."""
    return _masked_flash_call(q, k, v, keep, float(sm_scale), not on_tpu())
