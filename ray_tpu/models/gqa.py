"""Paged grouped-query attention as the classes that have it share it: one
mixer (`Attention`, a `models.paged.Mixer`) that knows its widths, its two
pools and which kernel reads them. `models.hybrid_ssm_moe.HybridSSMMoE`
runs it as it is; the others say what is their own in a subclass beside
the class (`models.gqa_window_moe`: a window over a ring, a rotation by
the layer's kind, a gate a head; `models.hybrid_delta`: q and k normed over
their whole width; `models.gated_conv_moe`: heads of 64, two a 128-lane of
a row, normed a head and rotated; `models.parallel_hybrid`: three scalars
and a rotation), so that each class's tests and cells guard the others'
attention. `models/decode.py` is the dense decoder's, stacked and scanned.

A pool is `(layers of the kind, pages, page_size, kv heads x head dim)`:
a position is one row of all its kv heads side by side, so a page is
contiguous and tiles as the paged decode kernels (`ops.paged_attention`)
copy it in. Which page a position lives on is `models/paged.py`'s.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp

from ray_tpu.models import regions as R
from ray_tpu.models.paged import (PAGED, RING, Cache, Mixer, Params, Pool,
                                  Walk)
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


class Attention(Mixer):
    """Causal grouped-query attention of `heads` query heads over
    `kv_heads` of `head_dim`, no bias, no rotation: a query sees every
    position, or its last `window` (the pools `names` are then a ring). A
    subclass says what its family adds in `_qkv` (a rotation, a norm, a
    scale) and `_out` (a gate, a scale), with their leaves in `shapes`."""

    closes = R.ATTN_OUT
    batched = True

    def __init__(self, d_model: int, heads: int, kv_heads: int,
                 head_dim: int, dtype, window: Optional[int] = None,
                 names: Tuple[str, str] = ("k", "v")):
        self.d_model, self.heads, self.kv_heads = d_model, heads, kv_heads
        self.head_dim, self.dtype, self.window = head_dim, dtype, window
        self.kv_dim = kv_heads * head_dim
        self.kind = PAGED if window is None else RING
        self.pools = tuple(Pool(name, self.kind, (self.kv_dim,), split=True,
                                window=window or 0) for name in names)

    def shapes(self, std: float, out_std: float) -> Dict[str, tuple]:
        e, q = self.d_model, self.heads * self.head_dim
        return {"wq": ((e, q), std), "wk": ((e, self.kv_dim), std),
                "wv": ((e, self.kv_dim), std), "wo": ((q, e), out_std)}

    def decode_kernel(self, page_size: int, dtype) -> str:
        """The walk over a lane's pages (over its ring under a window), or
        "einsum" where the kernel does not tile the pools: a pool row's
        width decides for heads narrower than a 128-lane."""
        if not _paged.uses_kernel(self.head_dim, page_size, dtype,
                                  self.kv_dim):
            return "einsum"
        return (_paged.KERNEL_PAGED_DECODE if self.window is None
                else _paged.KERNEL_PAGED_WINDOW_DECODE)

    def page_run(self, page_size: int, max_pages: int,
                 fixed: int = 0) -> int:
        """Pages one copy of the walk over its two pools brings: by what
        one layer's page of one pool weighs
        (`ops.paged_attention.decode_walk_run_pages`: 16 KB asks for 4, 8
        KB for 8, 32 KB and more for a page a copy); 1 over a ring, whose
        walk begins at any entry, and where a step traced here runs no
        kernel (the gather reads any table)."""
        if (self.window is not None
                or self.decode_kernel(page_size, self.dtype) == "einsum"):
            return 1
        page = self.pools[0].bytes(self.dtype, page_size)
        return _paged.decode_walk_run_pages(
            page, len(self.pools) * page, page_size, max_pages, fixed)

    # --------------------------------------------------------- pieces
    @R.region(R.ATTN_IN)
    def _qkv(self, layer: Params, h, at: Walk):
        """h (..., e) -> q (..., heads, hd), k, v (..., kv heads, hd)
        through the layer's `wq`, `wk`, `wv`, each split into heads as it
        is projected."""
        lead = h.shape[:-1]
        return tuple(
            (h @ layer[w].astype(self.dtype)).reshape(*lead, n,
                                                      self.head_dim)
            for w, n in (("wq", self.heads), ("wk", self.kv_heads),
                         ("wv", self.kv_heads)))

    @R.region(R.ATTN_OUT)
    def _out(self, layer: Params, h, out):
        """The heads' outputs `out` (..., heads, hd) of the layer's input
        `h`, through W_o."""
        out = out.astype(self.dtype)
        return out.reshape(*out.shape[:-2], -1) @ layer["wo"].astype(
            self.dtype)

    def _seq(self, layer: Params, h, at: Walk):
        """Causal attention over whole sequences h (b, s, e) through the
        flash forward. Returns (the output after W_o, k, v (b, s, kv heads,
        hd))."""
        q, k, v = self._qkv(layer, h, at)
        block_q, block_k = (FULL_BLOCKS if self.window is None
                            else SLIDING_BLOCKS)
        with R.region(R.ATTN_CORE):
            qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
            out = flash_attention(qt, kt, vt, causal=True, block_q=block_q,
                                  block_k=block_k, window=self.window)
            out = out.transpose(0, 2, 1, 3)
        return self._out(layer, h, out), k, v

    @R.region(R.ATTN_IN)
    def _write_prompt(self, pools: Cache, li: int, page_ids, k, v) -> Cache:
        """A prefill's keys and values of one sequence, k, v (1, s, kv
        heads, hd), written as whole pages into row `li` of the pools, in
        place (the rows past the prompt in its last page hold the padding's
        until the decode steps that reach them overwrite them); a page id
        past the pool drops its page. Returns the two pools."""
        n = page_ids.shape[0]
        out = {}
        for pool, a in zip(self.pools, (k, v)):
            held = pools[pool.name]
            s, page_size = a.shape[1], held.shape[2]
            a = jnp.pad(a[0].reshape(s, -1),
                        ((0, n * page_size - s), (0, 0)))
            out[pool.name] = held.at[li, page_ids].set(
                a.reshape(n, page_size, -1).astype(held.dtype), mode="drop")
        return out

    def _attend(self, pools: Cache, li: int, page, offset, q, k, v,
                page_tables, lengths, run: int = 1, fixed: int = 0):
        """One decode position a lane: its k, v (B, kv heads, hd) written
        at `(li, page, offset)` of the pools (a page past the pool writes
        nothing), then q (B, heads, hd) over the `lengths` positions the
        lane's table holds: every one, or under a window the last `window`
        in the ring `page_tables` names; `run`, `fixed`: the runs the
        tables are laid in behind their fixed entries (`Walk.run`,
        `Walk.fixed`; a ring's walk takes neither). Returns (out (B, heads,
        hd) in the pools' dtype, the two pools)."""
        B = q.shape[0]
        with R.region(R.ATTN_IN):
            out = {pool.name: pools[pool.name].at[li, page, offset].set(
                a.reshape(B, -1).astype(pools[pool.name].dtype), mode="drop")
                for pool, a in zip(self.pools, (k, v))}
            k_pool, v_pool = out.values()
            q = q.astype(k_pool.dtype)
        with R.region(R.ATTN_CORE):
            if self.window is None:
                return _paged.paged_decode_attention(
                    q, k_pool, v_pool, li, page_tables, lengths, run=run,
                    fixed=fixed), out
            return _paged.paged_window_decode_attention(
                q, k_pool, v_pool, li, page_tables, lengths,
                self.window), out

    # ------------------------------------------------------- forwards
    def hidden(self, layer: Params, h, at: Walk):
        return self._seq(layer, h, at)[0]

    def prefill(self, layer: Params, h, pools: Cache, li: int, at: Walk):
        one = h.ndim == 2               # a stream without a batch of one
        out, k, v = self._seq(layer, h[None] if one else h, at)
        if one:
            out = out[0]
        return out, self._write_prompt(pools, li, at.pages[self.kind], k, v)

    def decode_step(self, layer: Params, h, pools: Cache, li: int,
                    at: Walk):
        q, k, v = self._qkv(layer, h, at)
        page, tables = at.pages[self.kind]
        out, written = self._attend(pools, li, page, at.offset, q, k, v,
                                    tables, at.lengths, at.run, at.fixed)
        return self._out(layer, h, out), written
