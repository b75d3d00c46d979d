"""Multi-head latent attention (MLA) as the classes that have it share it
(`LatentAttention`, a `models.paged.Mixer`): `models.mla_moe.MLAMoE` (one
attention a layer), `models.shortcut_mla_moe.ShortcutMLAMoE` (two),
`models.hybrid_kda_moe.HybridKDAMoE` (one layer in six, no query LoRA, a
gate a head) and, under an indexer, `models.sparse_mla_moe.SparseMLAMoE`,
so that each class's tests and cells guard the others' attention.

With `x` the normed input of an attention and `a_q`, `a_kv` the config's two
LoRA scales (1 where the published model has none):

    c_q  = a_q RMSNorm(x W_qa)                  (q_lora_rank)
    q    = c_q W_qb        -> heads of [q_nope | q_rope]
           (x W_q in one matrix where the config has no q_lora_rank)
    [c_kv | k_rope] = x W_kva;  c_kv = a_kv RMSNorm(c_kv)
    k_rope = RoPE(k_rope)
    [k_nope | v] a head = c_kv W_kvb;           q_rope = RoPE(q_rope)
    scores = q . [k_nope | k_rope] / sqrt(nope + rope), causal softmax
    o = concat_h(P v) W_o     (a head's P v times sigmoid((x W_a)_h) first
                               where the config has a `head_gate`)

`a_q` multiplies the normed query latent (linear in it, so every head of
`q` carries it) and `a_kv` the normed key-value latent **as the cache holds
it**: a row is `[a_kv c_kv | k_rope | zeros]`, scaled once when it is
written, so `k_nope` and `v` carry the factor and `k_rope` does not, in the
expanded form (`W_kvb` of the scaled latent) and in the absorbed one (the
scaled row read as key and as value) alike, and no step pays for it again.

A pool row of the cache is one attention's `(pages, page_size, row_width)`;
`attn_expanded` is the prefill's form (keys and values through the flash
forward, which takes values narrower than keys) and `attn_absorbed` the
decode step's (`ops.paged_attention.mla_paged_decode_attention`).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import regions as R
from ray_tpu.models.config import ConfigDtypes
from ray_tpu.models.paged import (PAGED, RING, Cache, Mixer, Params,
                                  Pool, Walk)
from ray_tpu.ops import paged_attention as _paged
from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.norms import rms_norm_reference
from ray_tpu.ops.rope import apply_rope_cached, rope_cos_sin

# prefill's flash blocks (block_q, block_k), read on the chip alone at the
# three classes' heads and widths (20 of 256 / 256, 64 and 32 of 192 / 128)
# over buckets 256 to 16,384 (`tools/bench_flash.py --latent`; PERF.md
# section 6, PR 53): as large as the GQA classes' (`models/gqa.py`), and one
# pair for every bucket and width, the fastest of nine by 4 % or more
# wherever the bucket holds two blocks (0.45 ms at 2048 tokens and 20 heads
# against 0.51 at 512 x 512 and 1.94 at 128 x 128; 22.0 / 31.6 / 158.6 at
# 16,384: a grid step costs 0.4-0.5 us whatever it holds, which is what five
# blocks of 128's matmuls do). A shorter bucket is one block (the call cuts
# them to it), which is its fastest too. 1024 x 2048 is slower where it
# compiles (192 / 128: 57.0 ms against 55.5 at 16,384 and 64 heads) and past
# the kernel's fast memory at 256 / 256 from 8192 on
PREFILL_BLOCKS = (1024, 1024)
# a windowed prefill's flash blocks (block_q, block_k): `models/gqa.py`'s
# `SLIDING_BLOCKS`, a query block of 512 reaching two key blocks of 1024
WINDOW_PREFILL_BLOCKS = (512, 1024)


class LatentDims(ConfigDtypes):
    """What a config with the latent attention's fields (`n_heads`,
    `q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`,
    `v_head_dim`, `d_model`) derives from them."""

    # the LoRA scales; a config whose model has them overrides these
    q_lora_scale = 1.0
    kv_lora_scale = 1.0
    # a sigmoid gate a head on the attention's output, from a projection of
    # the attention's input; a config whose model has one overrides this
    head_gate = False

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """A cache row: latent and rotary key, padded to whole lanes."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128


class LatentAttention(Mixer):
    """The latent attention of `config` (a `LatentDims` with `rope_theta`
    and `norm_eps`); `layer` is the dict of one attention's leaves
    (`shapes`). Its pool `"kv"` holds one row an attention, which is the
    engine's `pool_rows`."""

    closes = R.ATTN_OUT
    batched = True
    # a sliding window's positions (`WindowLatentAttention`); None: a query
    # sees every causal position
    window = None

    def __init__(self, config):
        self.config, self.dtype = config, config.activation_dtype
        self.pools = (Pool("kv", PAGED, (config.row_width,),
                           an_attention=True),)

    def shapes(self, std: float, out_std: float
               ) -> Dict[str, Tuple[tuple, float]]:
        """(shape, init std) of one latent attention's leaves; std 0 means
        zeros (a norm scale, stored as w with the layer multiplying by 1 +
        w)."""
        c = self.config
        e, H = c.d_model, c.n_heads
        if c.q_lora_rank:
            query = {"wq_a": ((e, c.q_lora_rank), std),
                     "q_norm": ((c.q_lora_rank,), 0.0),
                     "wq_b": ((c.q_lora_rank, H * c.qk_head_dim), std)}
        else:                               # no LoRA: one matrix
            query = {"wq": ((e, H * c.qk_head_dim), std)}
        return {
            **query,
            "wkv_a": ((e, c.kv_lora_rank + c.qk_rope_head_dim), std),
            "kv_norm": ((c.kv_lora_rank,), 0.0),
            "wkv_b": ((c.kv_lora_rank,
                       H * (c.qk_nope_head_dim + c.v_head_dim)), std),
            "wo": ((H * c.v_head_dim, e), out_std),
            **({"w_head_gate": ((e, H), std)} if c.head_gate else {}),
        }

    def decode_kernel(self, page_size: int, dtype) -> str:
        """The latent kernel's name, or "einsum"."""
        c = self.config
        if _paged.mla_uses_kernel(c.row_width, c.kv_lora_rank, page_size,
                                  dtype):
            return _paged.KERNEL_MLA_PAGED_DECODE
        return "einsum"

    def page_run(self, page_size: int, max_pages: int,
                 fixed: int = 0) -> int:
        """Pages one copy of the latent kernel's walk brings: by what one
        layer's page of the pool weighs (`mla_walk_run_pages`: 20 KB of
        rows is under what a descriptor costs the scalar core); 1 where a
        step traced here runs no kernel (the gather reads any table)."""
        if self.decode_kernel(page_size, self.dtype) == "einsum":
            return 1
        return _paged.mla_walk_run_pages(
            self.pools[0].bytes(self.dtype, page_size), page_size, max_pages,
            fixed)

    def open(self, at: Walk) -> None:
        """The rotary part's cos and sin of the program's positions."""
        c = self.config
        with R.region(R.ATTN_IN):
            at.tables[self] = rope_cos_sin(
                at.positions(), c.qk_rope_head_dim, c.rope_theta)

    # --------------------------------------------------------- pieces
    @R.region(R.ATTN_IN)
    def _q(self, layer: Params, h):
        """h (..., e) -> q (..., heads, nope + rope), not yet rotated."""
        c = self.config
        ad = c.activation_dtype
        if c.q_lora_rank:
            c_q = rms_norm_reference(h @ layer["wq_a"].astype(ad),
                                     layer["q_norm"], c.norm_eps)
            if c.q_lora_scale != 1.0:
                c_q = c_q * jnp.asarray(c.q_lora_scale, ad)
            q = c_q @ layer["wq_b"].astype(ad)
        else:
            q = h @ layer["wq"].astype(ad)
        return q.reshape(*h.shape[:-1], c.n_heads, c.qk_head_dim)

    @R.region(R.ATTN_OUT)
    def _gated(self, layer: Params, h, out):
        """The heads' outputs out (..., heads, v), each head's times the
        sigmoid of its number in `h W_a` where the config has a
        `head_gate`, flattened to (..., heads * v)."""
        c = self.config
        if c.head_gate:
            gate = jax.nn.sigmoid((h @ layer["w_head_gate"].astype(
                c.activation_dtype)).astype(jnp.float32))
            out = (out.astype(jnp.float32) * gate[..., None]).astype(
                out.dtype)
        return out.reshape(*h.shape[:-1], c.n_heads * c.v_head_dim)

    @R.region(R.ATTN_IN)
    def _latent(self, layer: Params, h, cos, sin):
        """h (..., e) -> the cache's row parts: c_kv (..., latent) after
        its norm (and its scale) and k_rope (..., rope) after the
        rotation."""
        c = self.config
        ad = c.activation_dtype
        kv = h @ layer["wkv_a"].astype(ad)
        c_kv = kv[..., :c.kv_lora_rank]
        if c.kv_lora_scale != 1.0:      # scaled in float32, rounded once
            c_kv = (rms_norm_reference(c_kv.astype(jnp.float32),
                                       layer["kv_norm"], c.norm_eps)
                    * c.kv_lora_scale).astype(ad)
        else:
            c_kv = rms_norm_reference(c_kv, layer["kv_norm"], c.norm_eps)
        k_rope = apply_rope_cached(kv[..., None, c.kv_lora_rank:], cos, sin)
        return c_kv, k_rope[..., 0, :]

    def _rows(self, c_kv, k_rope, dtype):
        """The rows a cache page holds: [c_kv | k_rope | zeros]."""
        c = self.config
        pad = c.row_width - c.kv_lora_rank - c.qk_rope_head_dim
        rows = jnp.concatenate([c_kv, k_rope], axis=-1).astype(dtype)
        return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)])

    def _wkv_b(self, layer: Params):
        """W_kvb as (latent, heads, nope + v)."""
        c = self.config
        return layer["wkv_b"].astype(c.activation_dtype).reshape(
            c.kv_lora_rank, c.n_heads, c.qk_nope_head_dim + c.v_head_dim)

    def _attn_expanded(self, layer: Params, h, cos, sin):
        """Causal MLA over whole sequences, keys and values expanded from
        the latent. h (b, s, e). Returns (attention output before W_o
        (b, s, heads * v), c_kv, k_rope)."""
        c = self.config
        b, s, _ = h.shape
        nope = c.qk_nope_head_dim
        q = self._q(layer, h)                           # (b, s, H, qk)
        with R.region(R.ATTN_IN):
            q = jnp.concatenate(
                [q[..., :nope], apply_rope_cached(q[..., nope:], cos, sin)],
                axis=-1)
        c_kv, k_rope = self._latent(layer, h, cos, sin)
        with R.region(R.ATTN_IN):
            kv = jnp.einsum("bsc,chd->bshd", c_kv, self._wkv_b(layer))
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(
                    k_rope[:, :, None, :], (b, s, c.n_heads,
                                            c.qk_rope_head_dim))], axis=-1)
            v = kv[..., nope:]
            if self.window:
                # the windowed flash forward takes values as wide as its
                # keys: padded with zeros, the output cut back
                v = jnp.pad(v, ((0, 0),) * 3 + (
                    (0, max(0, c.qk_head_dim - c.v_head_dim)),))
            qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        block_q, block_k = PREFILL_BLOCKS
        window = {}
        if self.window:
            block_q, block_k = WINDOW_PREFILL_BLOCKS
            window = {"window": self.window}
        with R.region(R.ATTN_CORE):
            out = flash_attention(qt, kt, vt, causal=True,
                                  sm_scale=1.0 / math.sqrt(c.qk_head_dim),
                                  block_q=block_q, block_k=block_k,
                                  **window)
        with R.region(R.ATTN_OUT):
            if self.window:
                out = out[..., :c.v_head_dim]
            out = out.transpose(0, 2, 1, 3)
        out = self._gated(layer, h, out)
        return out, c_kv, k_rope

    @R.region(R.ATTN_IN)
    def _write_pages(self, pool, row: int, c_kv, k_rope, page_ids,
                     page_size: int):
        """A prefill's rows of one sequence (c_kv (s, latent), k_rope
        (s, rope)) written into pool row `row` as whole pages, in place;
        a page id of `num_pages` drops its page."""
        n = page_ids.shape[0]
        rows = self._rows(c_kv, k_rope, pool.dtype)
        rows = jnp.pad(rows, ((0, n * page_size - rows.shape[0]), (0, 0)))
        return pool.at[row, page_ids].set(
            rows.reshape(n, page_size, self.config.row_width), mode="drop")

    def _attn_absorbed(self, layer: Params, h, cos, sin, pool, row: int,
                       wr_page, wr_slot, page_tables, lengths, run: int = 1,
                       fixed: int = 0):
        """One decode position a lane in the absorbed form: `q_lat = q_nope
        W_UK^T`, scores `q_lat . c_kv + q_rope . k_rope`, `o_lat = P c_kv`,
        `o = o_lat W_UV`, over pool row `row`, which first gets this
        position's row (`wr_page` of `num_pages` writes nothing); `run`,
        `fixed`: the runs the tables are laid in behind their fixed entries
        (`Walk.run`, `Walk.fixed`). h (B, e). Returns (attention output
        before W_o (B, heads * v), pool)."""
        c = self.config
        ad = c.activation_dtype
        nope, latent = c.qk_nope_head_dim, c.kv_lora_rank
        sm_scale = 1.0 / math.sqrt(c.qk_head_dim)
        pad = c.row_width - latent - c.qk_rope_head_dim
        q = self._q(layer, h)                           # (B, H, qk)
        c_kv, k_rope = self._latent(layer, h, cos, sin)
        with R.region(R.ATTN_IN):
            pool = pool.at[row, wr_page, wr_slot].set(
                self._rows(c_kv, k_rope, pool.dtype), mode="drop")
            w_kvb = self._wkv_b(layer)
            q_lat = jnp.einsum("bhn,chn->bhc", q[..., :nope],
                               w_kvb[..., :nope])
            q_rope = apply_rope_cached(q[..., nope:], cos, sin)
            q_row = jnp.pad(jnp.concatenate([q_lat, q_rope], axis=-1),
                            ((0, 0), (0, 0), (0, pad))).astype(pool.dtype)
        with R.region(R.ATTN_CORE):
            o_lat = self._walk(q_row, pool, row, page_tables, lengths,
                               latent, sm_scale, run, fixed)
        with R.region(R.ATTN_OUT):
            out = jnp.einsum("bhc,chv->bhv", o_lat.astype(ad),
                             w_kvb[..., nope:])
        return self._gated(layer, h, out), pool

    def _walk(self, q_row, pool, row: int, page_tables, lengths,
              latent: int, sm_scale: float, run: int, fixed: int):
        """The lanes' rows of the latent over what their tables hold."""
        return _paged.mla_paged_decode_attention(
            q_row, pool, row, page_tables, lengths, latent, sm_scale, run,
            fixed)

    # ------------------------------------------------------- forwards
    def _prompt(self, layer: Params, h, pools: Cache, li: int, at: Walk):
        """A prompt h (1, s, e) in the expanded form, its rows written as
        whole pages in place. Returns (the attention's output before W_o,
        the pools written)."""
        attn, c_kv, k_rope = self._attn_expanded(layer, h, *at.tables[self])
        kept = self.pools[0]            # a ring's pages: the last it holds
        pool = pools[kept.name]
        return attn, {kept.name: self._write_pages(
            pool, li, c_kv[0], k_rope[0], at.pages[kept.kind],
            pool.shape[2])}

    def _lanes(self, layer: Params, h, pools: Cache, li: int, at: Walk):
        """A decode step's lanes h (B, e) in the absorbed form. Returns
        (the attention's output before W_o, the pools written)."""
        page, tables = at.pages[PAGED]
        out, pool = self._attn_absorbed(
            layer, h, *at.tables[self], pools["kv"], li, page, at.offset,
            tables, at.lengths, at.run, at.fixed)
        return out, {"kv": pool}

    def hidden(self, layer: Params, h, at: Walk):
        attn, _, _ = self._attn_expanded(layer, h, *at.tables[self])
        with R.region(R.ATTN_OUT):
            return attn @ layer["wo"].astype(self.dtype)

    def prefill(self, layer: Params, h, pools: Cache, li: int, at: Walk):
        one = h.ndim == 2               # a stream without a batch of one
        attn, written = self._prompt(layer, h[None] if one else h, pools,
                                     li, at)
        with R.region(R.ATTN_OUT):
            return (attn[0] if one else attn) @ layer["wo"].astype(
                self.dtype), written

    def decode_step(self, layer: Params, h, pools: Cache, li: int,
                    at: Walk):
        out, written = self._lanes(layer, h, pools, li, at)
        with R.region(R.ATTN_OUT):
            return out @ layer["wo"].astype(self.dtype), written


# what a decode step's ring walks did, summed over the ring's layers: the
# positions they copied in (whole pages from the first the window reaches)
# and the positions the lanes saw of them (at most `window` a lane and layer)
RING_COUNTS = ("ring_positions_read", "ring_positions_seen")


class WindowLatentAttention(LatentAttention):
    """`LatentAttention` under a sliding window: a query sees its last
    `window` positions, itself among them, and the cache keeps a sequence's
    last rows only, in a ring of the allocator's fixed class
    (`ops.paged_attention.ring_pages(window)` pages a sequence, logical page
    j at table entry `j mod ring`) under the pool `pool`. `config` is the
    layer kind's own `LatentDims` (a class may hold two geometries). A
    prefill is the windowed flash forward in the expanded form and writes
    the last pages the ring holds; a decode step the absorbed form through
    `mla_paged_window_decode_attention`. A lane shorter than the window
    reads what `LatentAttention` reads of the same rows. What a step's
    walks did is summed in `"ring_step"` (`RING_COUNTS`)."""

    counts = ("ring_step", RING_COUNTS)

    def __init__(self, config, window: int, pool: str = "kv_w"):
        self.config, self.dtype = config, config.activation_dtype
        self.window = int(window)
        self.pools = (Pool(pool, RING, (config.row_width,),
                           window=self.window, an_attention=True),)

    def decode_kernel(self, page_size: int, dtype) -> str:
        """The latent window kernel's name, or "einsum"."""
        c = self.config
        if _paged.mla_uses_kernel(c.row_width, c.kv_lora_rank, page_size,
                                  dtype):
            return _paged.KERNEL_MLA_PAGED_WINDOW_DECODE
        return "einsum"

    def page_run(self, page_size: int, max_pages: int,
                 fixed: int = 0) -> int:
        """A ring's walk begins at any entry: a page a copy."""
        return 1

    def _walk(self, q_row, pool, row: int, ring_tables, lengths,
              latent: int, sm_scale: float, run: int, fixed: int):
        """The lanes' rows over their rings: `ring_tables` (B, ring) the
        lanes' first table entries."""
        return _paged.mla_paged_window_decode_attention(
            q_row, pool, row, ring_tables, lengths, latent, sm_scale,
            self.window)

    def _lanes(self, layer: Params, h, pools: Cache, li: int, at: Walk):
        """A decode step's lanes over their rings; what the walk copied in
        and what the lanes saw of it added to the step's counts."""
        name = self.pools[0].name
        page, tables = at.pages[RING]
        out, pool = self._attn_absorbed(
            layer, h, *at.tables[self], pools[name], li, page, at.offset,
            tables, at.lengths)
        with R.region(R.CACHE):
            size, n = pool.shape[2], at.lengths
            first = jnp.maximum(n - self.window, 0) // size
            counts = (jnp.sum((-(-n // size) - first) * size),
                      jnp.sum(jnp.minimum(n, self.window)))
            step = {key: pools["ring_step"][key] + c.astype(jnp.int32)
                    for key, c in zip(RING_COUNTS, counts)}
        return out, {name: pool, "ring_step": step}
