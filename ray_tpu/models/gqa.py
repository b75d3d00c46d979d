"""Paged grouped-query attention as the classes that have it share it:
`models.gqa_window_moe.GQAWindowMoE` (two pairs of pools, one a ring under
a sliding window), `models.hybrid_delta.HybridDelta`,
`models.hybrid_ssm_moe.HybridSSMMoE` and `models.gated_conv_moe.
GatedConvMoE` (one pair, for the layers that are attention; the last at
heads of 64, two a 128-lane of a row) and `models.parallel_hybrid.
ParallelHybrid` (one pair over all layers), so that each class's tests and
cells guard the others' attention. `models/decode.py` is the dense decoder's, stacked and scanned.

A pool is `(layers of the kind, pages, page_size, kv heads x head dim)`:
a position is one row of all its kv heads side by side, so a page is
contiguous and tiles as the paged decode kernels (`ops.paged_attention`)
copy it in. Which page a position lives on is `models/paged.py`'s; here
are the forms of the attention and what a page of it costs. What differs
between the classes (head counts, a window, a rotation or a norm of q and
k, an output gate) stays with them: these take shapes and arrays.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax.numpy as jnp

from ray_tpu.models import regions as R
from ray_tpu.models.paged import Cache, Params
from ray_tpu.ops import paged_attention as _paged
from ray_tpu.ops.attention import flash_attention

# prefill's flash blocks (block_q, block_k), read on the chip at 1k, 4k
# and 8k tokens (PERF.md, PR 35): a full layer's, as large as training's
# (8.3 ms at 8192 tokens and 48 heads against 16.9 at 512 and 84 at 128:
# a grid step costs what a small block's matmuls do); a sliding layer's
# query block of 512 reaches two key blocks of 1024 (4.3 ms at 8192 tokens
# and 64 heads against 8.9 at 256 x 256, where less is computed and masked
# but the steps are four times as many)
FULL_BLOCKS = (1024, 1024)
SLIDING_BLOCKS = (512, 1024)


@R.region(R.ATTN_IN)
def qkv(layer: Params, h, heads: int, kv_heads: int, head_dim: int, dtype):
    """h (..., e) -> q (..., heads, hd), k, v (..., kv heads, hd) through
    the layer's `wq`, `wk`, `wv`, each split into heads as it is
    projected."""
    lead = h.shape[:-1]
    return tuple(
        (h @ layer[w].astype(dtype)).reshape(*lead, n, head_dim)
        for w, n in (("wq", heads), ("wk", kv_heads), ("wv", kv_heads)))


@R.region(R.ATTN_CORE)
def attend_seq(q, k, v, window: Optional[int] = None):
    """Causal attention over whole sequences through the flash forward, a
    query seeing its last `window` keys where one is given: q (b, s,
    heads, hd), k, v (b, s, kv heads, hd) -> (b, s, heads, hd)."""
    block_q, block_k = FULL_BLOCKS if window is None else SLIDING_BLOCKS
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    out = flash_attention(qt, kt, vt, causal=True, block_q=block_q,
                          block_k=block_k, window=window)
    return out.transpose(0, 2, 1, 3)


@R.region(R.ATTN_IN)
def write_prompt(pools: Cache, names: Tuple[str, str], li: int, page_ids,
                 k, v) -> Cache:
    """A prefill's keys and values of one sequence, k, v (1, s, kv heads,
    hd), written as whole pages into row `li` of the pools `names`, in
    place (the rows past the prompt in its last page hold the padding's
    until the decode steps that reach them overwrite them); a page id past
    the pool drops its page. Returns the two pools."""
    n = page_ids.shape[0]
    out = {}
    for name, a in zip(names, (k, v)):
        pool = pools[name]
        s, page_size = a.shape[1], pool.shape[2]
        a = jnp.pad(a[0].reshape(s, -1), ((0, n * page_size - s), (0, 0)))
        out[name] = pool.at[li, page_ids].set(
            a.reshape(n, page_size, -1).astype(pool.dtype), mode="drop")
    return out


def decode_attend(pools: Cache, names: Tuple[str, str], li: int, page,
                  offset, q, k, v, page_tables, lengths,
                  window: Optional[int] = None):
    """One decode position a lane: its k, v (B, kv heads, hd) written at
    `(li, page, offset)` of the pools `names` (a page past the pool writes
    nothing), then q (B, heads, hd) over the `lengths` positions the lane's
    table holds: every one, or under a `window` the last `window` in the
    ring `page_tables` names. Returns (out (B, heads, hd) in the pools'
    dtype, the two pools)."""
    B = q.shape[0]
    with R.region(R.ATTN_IN):
        out = {name: pools[name].at[li, page, offset].set(
            a.reshape(B, -1).astype(pools[name].dtype), mode="drop")
            for name, a in zip(names, (k, v))}
        k_pool, v_pool = (out[name] for name in names)
        q = q.astype(k_pool.dtype)
    with R.region(R.ATTN_CORE):
        if window is None:
            return _paged.paged_decode_attention(
                q, k_pool, v_pool, li, page_tables, lengths), out
        return _paged.paged_window_decode_attention(
            q, k_pool, v_pool, li, page_tables, lengths, window), out


# ------------------------------------------------ what an engine asks
def layer_page_bytes(kv_dim: int, page_size: int, dtype,
                     tp_shards: int = 1) -> int:
    """Bytes of one layer's page of keys and values on a shard: what a
    pool of `layers` layers costs a page is `layers` times this, and what
    the kernel's walk is asked by (`walk_block_pages`)."""
    return (2 * page_size * (kv_dim // max(1, tp_shards))
            * jnp.dtype(dtype).itemsize)


def walk_block_pages(kv_dim: int, page_size: int, max_pages: int,
                     dtype) -> int:
    """Pages a block of a layer's walk holds over tables of `max_pages`."""
    return _paged.walk_block_pages(
        layer_page_bytes(kv_dim, page_size, dtype), page_size, max_pages)


def decode_kernels(head_dim: int, page_size: int, dtype,
                   kernels: Sequence[Tuple[str, Any]],
                   kv_dim: int = 0) -> str:
    """Which kernels a decode step holds, for `decode_attention`: the
    names of `kernels` ((name, whether the model has such layers)) joined,
    or "einsum" where the paged kernel does not tile the pools (the
    attention layers gather). `kv_dim`: a pool row's width, which decides
    for heads narrower than a 128-lane."""
    if not _paged.uses_kernel(head_dim, page_size, dtype, kv_dim):
        return "einsum"
    return "+".join(name for name, present in kernels if present)
