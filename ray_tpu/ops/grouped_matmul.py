"""Grouped matmul: rows sorted by group, each group times its own matrix.

`out[r] = lhs[r] @ rhs[g]` for the rows `r` of group `g`; the rows of a
group lie together (`group_sizes` says how many each has) and rows past
the last group come back as zeros. It is the expert matmul of a dropless
mixture of experts (`models/moe.py`): the (token, expert) pairs sorted by
expert are the rows, the experts' matrices the groups.

On a TPU it is the Pallas kernel `moe_gmm`: the rows are cut into tiles
and the kernel walks the (group, tile) pairs that share rows, a list made
from `group_sizes` outside the kernel and handed in by scalar prefetch, so
a group without rows is never visited and its matrix never read; a tile
that several groups share is visited once a group and each visit keeps its
own rows (a masked store into the output block, which stays in VMEM while
the visits last). The contraction is whole in one block (k is a model
width, a few thousand), so there is no accumulator across grid steps.
Elsewhere it is `jax.lax.ragged_dot`, which gives the same numbers. The
backward of both is `ragged_dot`'s own (training through experts on the
chip has not been run: PERF.md section 7).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.dispatch import on_tpu

# The kernel's name on the device's clock (see attention.KERNEL_FWD).
KERNEL_GMM = "moe_gmm"

# The most rows a tile holds: under the rows the MXU multiplies in the time
# a group's block takes to arrive (197 TFLOP/s over 819 GB/s is 240 rows of
# bf16 on a v5e, whatever k and n), so that a work item which fetches a
# block is never longer than the fetch.
MAX_TILE_ROWS = 128
# The most bytes one block of a group's matrix holds (two are in flight).
RHS_BLOCK_BYTES = 8 << 20


def gmm_tile_shape(m: int, k: int, n: int, dtype):
    """The tiles the kernel cuts these shapes into: (rows a tile, output
    columns a block), or None where they do not cut into whole
    `(sublane, 128)` tiles. From the call's static shapes and nothing else.

    A work item multiplies a whole tile of rows by one group's block and
    keeps the group's own rows. Up to `MAX_TILE_ROWS` the rows it throws away
    cost nothing, since the item waits for its block that long anyway, and
    every item is a grid step: so the row tile is the largest power of two
    in `m` up to there, whether a group has one row or a thousand (swept on
    the chip at 1 to 1024 rows a group: PERF.md section 6, PR 36). A column
    block is the whole `n` where the group's matrix fits the block's bytes,
    one contiguous read and one grid step; else the widest multiple of 128
    that divides `n` and fits."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    tm = min(MAX_TILE_ROWS, m & -m)     # the power of two in m
    if tm < sublanes or k % 128 or n % 128:
        return None
    tn = max(c for c in range(128, n + 1, 128) if n % c == 0
             and (c == 128 or k * c * itemsize <= RHS_BLOCK_BYTES))
    return tm, tn


def gmm_vmem_bytes(tm: int, tn: int, k: int, dtype) -> int:
    """The `vmem_limit_bytes` of a call with these tiles: two of each
    block in flight, the float32 product beside them, and room for the
    compiler's own."""
    itemsize = jnp.dtype(dtype).itemsize
    return (2 * itemsize * (tm * k + k * tn + tm * tn) + 4 * tm * tn
            + (4 << 20))


def gmm_tiles(m: int, k: int, n: int, dtype) -> bool:
    """Whether the kernel tiles these shapes."""
    return gmm_tile_shape(m, k, n, dtype) is not None


def work_list(group_sizes, m: int, tm: int):
    """The (group, tile) pairs that share rows, in row order, padded to the
    static length `m // tm + groups - 1` with copies of the last pair (the
    kernel does nothing there and no block moves). Returns (groups, tiles,
    starts, ends, count): int32 arrays and the number of real pairs."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles_of = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    work_end = jnp.cumsum(tiles_of)
    count = work_end[-1]
    n_work = m // tm + G - 1
    w = jnp.arange(n_work, dtype=jnp.int32)
    # the group of work item w: the first whose items end past w
    group = jnp.searchsorted(work_end, w, side="right").astype(jnp.int32)
    group = jnp.minimum(group, G - 1)
    tile = first[group] + w - (work_end[group] - tiles_of[group])
    last = jnp.maximum(count - 1, 0)
    real = w < count
    group = jnp.where(real, group, group[last])
    tile = jnp.where(real, tile, tile[last])
    tile = jnp.clip(tile, 0, m // tm - 1).astype(jnp.int32)
    return group, tile, starts, ends, count.reshape(1)


def _gmm_kernel(group_ref, tile_ref, start_ref, end_ref, count_ref,
                lhs_ref, rhs_ref, out_ref, *, tm: int):
    w = pl.program_id(1)
    g, t = group_ref[w], tile_ref[w]
    new_tile = (w == 0) | (tile_ref[jnp.maximum(w - 1, 0)] != t)

    @pl.when(new_tile)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(w < count_ref[0])
    def _():
        acc = lax.dot_general(lhs_ref[...], rhs_ref[0],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
        rows = t * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (rows >= start_ref[g]) & (rows < end_ref[g])
        out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype),
                                 out_ref[...])


@functools.partial(jax.jit, static_argnames="interpret")
def _gmm_call(lhs, rhs, group_sizes, interpret: bool):
    m, k = lhs.shape
    G, _, n = rhs.shape
    tm, tn = gmm_tile_shape(m, k, n, lhs.dtype)
    group, tile, starts, ends, count = work_list(group_sizes, m, tm)
    n_work = group.shape[0]
    call = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, n_work),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, w, g, t, *_: (t[w], 0)),
                pl.BlockSpec((1, k, tn),
                             lambda j, w, g, t, *_: (g[w], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, w, g, t, *_: (t[w], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=gmm_vmem_bytes(tm, tn, k, lhs.dtype)),
        interpret=interpret,
        name=KERNEL_GMM,
    )
    out = call(group, tile, starts, ends, count, lhs, rhs)
    # a tile past the last group's rows is never visited, and holds
    # whatever the buffer held
    live = jnp.arange(m)[:, None] < ends[-1]
    return jnp.where(live, out, jnp.zeros_like(out))


def uses_kernel(m: int, k: int, n: int, dtype) -> bool:
    """What `grouped_matmul` decides: by the platform being traced for and
    the shapes, and by nothing else."""
    return on_tpu() and gmm_tiles(m, k, n, dtype)


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """lhs (m, k), rows sorted by group; rhs (groups, k, n); group_sizes
    (groups,) int32 summing to at most m. Returns (m, n) in lhs's dtype,
    zeros in the rows past the last group."""
    if uses_kernel(*lhs.shape, rhs.shape[2], lhs.dtype):
        return _gmm_call(lhs, rhs.astype(lhs.dtype), group_sizes, False)
    return lax.ragged_dot(lhs, rhs.astype(lhs.dtype),
                          group_sizes.astype(jnp.int32))


def _gmm_fwd(lhs, rhs, group_sizes):
    return grouped_matmul(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _gmm_bwd(res, g):
    lhs, rhs, group_sizes = res
    _, vjp = jax.vjp(
        lambda a, b: lax.ragged_dot(a, b.astype(a.dtype),
                                    group_sizes.astype(jnp.int32)),
        lhs, rhs)
    return (*vjp(g), None)


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul_kernel(lhs, rhs, group_sizes):
    """Force the Pallas kernel path (interpreter off-TPU) — test hook."""
    return _gmm_call(lhs, rhs.astype(lhs.dtype), group_sizes, not on_tpu())
