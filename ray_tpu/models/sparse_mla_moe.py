"""`MLAMoE` whose attention reads only the positions a learned indexer
chooses (`glm_moe_dsa`: GLM-5; the DeepSeek sparse attention over a latent
cache), one chip's share of its experts held, on the ops `MLAMoE` runs on
and behind the same serving engine.

Every layer, dense ones too, has an indexer beside its latent attention.
With `x` the normed input and `c_q = RMSNorm(x W_qa)` the query latent the
main attention forms anyway:

    q^I_j = RoPE(c_q W^I_q)_j        j = 1..index_n_heads, index_head_dim
    k^I   = RoPE(LayerNorm(x W^I_k)) one a position for all heads
    w_j   = (x W^I_w)_j / sqrt(index_n_heads * index_head_dim)
    I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])     s <= t, float32
    S_t   = the min(index_topk, t + 1) positions of largest I[t, s]

(the rotary on the first `qk_rope_head_dim` of the `index_head_dim`, split
halves, at the main attention's angles) and the main attention, `models.
latent`'s, takes its softmax over `S_t` alone. Up to `index_topk` positions
that is every position: the layer is then exactly `MLAMoE`'s, and a program
whose context cannot pass `index_topk` (a prefill bucket, a decode step's
tables) is traced as `MLAMoE`'s, with the index keys written besides.

**The cache** holds the latent pool `"kv"` `(layers, pages, page_size,
row_width)` and beside it the index keys `"idx"` `(layers, pages,
page_size, index_head_dim)` **under the same page ids**: a page of the
allocator names both, `cache_page_bytes` prices both, and nothing of the
engine or the allocator knows there are two. A prefill writes both as whole
pages, a decode step one row of each.

A decode step, a layer: both rows written; the indexer's scores of every
position the lanes hold and the choice of `index_topk` of them
(`ops.sparse_attention.choose_paged`: a walk over the live pages of the
index pool and a threshold, or a gather and `lax.top_k`), then the absorbed
attention over the chosen rows (`attend_chosen`: a walk over every live
latent row that keeps the chosen, or a gather of them). A prefill past
`index_topk`: `prefill_keep_mask` (a tile kernel for the scores, the
threshold by bisection, an int8 mask) and the flash forward that reads the
mask (`masked_flash_attention`). Both in `r.attn_index` / `r.attn_core`
(`models/regions.py`). An expert layer routes a long prefill's tokens
`FFN_ROWS` at a time.

**The experts**: the layer is told which experts it holds (`experts_held`
= (first, count) of `n_routed_experts`, as `ShortcutMLAMoE`): it routes
over all of them, computes its own experts' rows and the shared expert,
and leaves out what the experts held elsewhere would add.

Beside `paged.ExpertCounts`' entries (`"moe_step"` all of
`moe.STEP_COUNTS`) the cache carries `"dsa_step"`, the last decode step's
`DSA_COUNTS` summed over the layers: `dsa_positions_scored` (positions the
indexer scored), `dsa_positions_selected` (positions the attention read)
and `dsa_lanes_past_topk` (lanes, counted once a layer, that hold more than
`index_topk` positions).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import regions as R
from ray_tpu.models.latent import LatentAttention
from ray_tpu.models.mla_moe import MLAMoE, MLAMoEConfig
from ray_tpu.models.moe import STEP_COUNTS
from ray_tpu.models.paged import PAGED, Cache, Params, Pool, Walk
from ray_tpu.ops import paged_attention as _paged
from ray_tpu.ops import sparse_attention as _sparse
from ray_tpu.ops.norms import layer_norm, rms_norm_reference
from ray_tpu.ops.rope import apply_rope_cached, rotate_leading

DSA_COUNTS = ("dsa_positions_scored", "dsa_positions_selected",
              "dsa_lanes_past_topk")
# Tokens an expert layer routes at once: every (token, choice) pair is a
# row of the grouped matmuls whether its expert is held here or not, and
# 16,384 tokens x 8 choices x 6144 float32 are 3 GB a copy; a prefill past
# this many is routed block by block
FFN_ROWS = 2048


@dataclasses.dataclass(frozen=True)
class SparseMLAMoEConfig(MLAMoEConfig):
    """`MLAMoEConfig` and the indexer's three sizes under their published
    keys (`config.json` of `glm_moe_dsa`); `n_routed_experts` counts the
    experts of the whole layer and `experts_held` this chip's."""
    d_model: int = 6144
    n_layers: int = 78
    n_heads: int = 64
    q_lora_rank: int = 2048
    d_ff: int = 12288
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 3
    routed_scaling_factor: float = 2.5
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    index_norm_eps: float = 1e-6            # the index key's LayerNorm
    experts_held: Optional[Tuple[int, int]] = None   # (first, count); all

    def __post_init__(self):
        super().__post_init__()
        first, count = self.held
        if count < 1 or not 0 <= first <= self.n_routed_experts - count:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.n_routed_experts} experts")
        if self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError("the indexer's rotary part is the main "
                             "attention's: index_head_dim >= "
                             "qk_rope_head_dim")

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the experts this chip holds."""
        return tuple(self.experts_held or (0, self.n_routed_experts))


def tiny_sparse_mla_moe(vocab_size: int = 256, experts_held=(4, 4),
                        index_topk: int = 32) -> SparseMLAMoEConfig:
    """CI/debug model: every mechanism at a size the CPU runs in seconds
    (contexts that pass `index_topk`, a share of the experts that does not
    start at 0, an index key wider than its rotary part)."""
    return SparseMLAMoEConfig(
        vocab_size=vocab_size, d_model=64, n_layers=3, n_heads=4,
        q_lora_rank=48, kv_lora_rank=96, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=32, d_ff=128,
        moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1,
        num_experts_per_tok=2, first_k_dense_replace=1,
        index_n_heads=16, index_head_dim=32, index_topk=index_topk,
        experts_held=experts_held, max_seq_len=128,
        dtype="float32", param_dtype="float32")


class SparseLatentAttention(LatentAttention):
    """`LatentAttention` under the indexer: beside the latent pool the
    index keys `"idx"` under the same page ids, and what a step's indexer
    did in `"dsa_step"`."""

    counts = ("dsa_step", DSA_COUNTS)

    def __init__(self, config: SparseMLAMoEConfig):
        super().__init__(config)
        self.pools += (Pool("idx", PAGED, (config.index_head_dim,)),)

    def index_shapes(self, std: float) -> Dict[str, tuple]:
        """The indexer's leaves: `wq_idx` from the query latent, `wk_idx`
        and `w_idx` from the normed stream, the key's LayerNorm (scale
        stored as w, the layer multiplying by 1 + w, and a bias)."""
        c = self.config
        return {"wq_idx": ((c.q_lora_rank,
                            c.index_n_heads * c.index_head_dim), std),
                "wk_idx": ((c.d_model, c.index_head_dim), std),
                "k_idx_norm": ((c.index_head_dim,), 0.0),
                "k_idx_bias": ((c.index_head_dim,), 0.0),
                "w_idx": ((c.d_model, c.index_n_heads), std)}

    # --------------------------------------------------------- pieces
    @R.region(R.ATTN_IN)
    def _q_latent(self, layer: Params, h):
        """h (..., e) -> (c_q (..., q_lora) the normed query latent, q
        (..., heads, nope + rope) not yet rotated), as `LatentAttention.
        _q` forms them."""
        c = self.config
        ad = c.activation_dtype
        c_q = rms_norm_reference(h @ layer["wq_a"].astype(ad),
                                 layer["q_norm"], c.norm_eps)
        if c.q_lora_scale != 1.0:       # the indexer reads it scaled too
            c_q = c_q * jnp.asarray(c.q_lora_scale, ad)
        q = c_q @ layer["wq_b"].astype(ad)
        return c_q, q.reshape(*h.shape[:-1], c.n_heads, c.qk_head_dim)

    @R.region(R.ATTN_INDEX)
    def _index_key(self, layer: Params, h, cos, sin):
        """h (..., e) -> the position's index key (..., index_head_dim),
        normed and rotated: the index pool's row."""
        c = self.config
        ad = c.activation_dtype
        k = layer_norm(h @ layer["wk_idx"].astype(ad),
                       1.0 + layer["k_idx_norm"].astype(jnp.float32),
                       layer["k_idx_bias"], c.index_norm_eps)
        return rotate_leading(k[..., None, :], cos, sin)[..., 0, :]

    @R.region(R.ATTN_INDEX)
    def _index_query(self, layer: Params, h, c_q, cos, sin):
        """(q^I (..., heads, index_head_dim) rotated, w (..., heads)
        float32 with both scales in it)."""
        c = self.config
        ad = c.activation_dtype
        q = (c_q @ layer["wq_idx"].astype(ad)).reshape(
            *h.shape[:-1], c.index_n_heads, c.index_head_dim)
        w = (h @ layer["w_idx"].astype(ad)).astype(jnp.float32) / math.sqrt(
            c.index_n_heads * c.index_head_dim)
        return rotate_leading(q, cos, sin), w

    @R.region(R.ATTN_INDEX)
    def _write_index_pages(self, idx_pool, row: int, k_idx, page_ids,
                           page_size: int):
        """A prefill's index keys k_idx (s, width) written into row `row`
        of the index pool as whole pages, in place, under the page ids the
        latent rows go to."""
        n = page_ids.shape[0]
        rows = jnp.pad(k_idx.astype(idx_pool.dtype),
                       ((0, n * page_size - k_idx.shape[0]), (0, 0)))
        return idx_pool.at[row, page_ids].set(
            rows.reshape(n, page_size, k_idx.shape[-1]), mode="drop")

    def _attn_selected(self, layer: Params, h, cos, sin):
        """Causal MLA over whole sequences in the expanded form, each
        query's softmax over its indexer's set; a sequence no longer than
        `index_topk` is `_attn_expanded`'s. h (1, s, e). Returns (attention
        output before W_o (1, s, heads * v), c_kv, k_rope, k_idx)."""
        c = self.config
        s = h.shape[1]
        k_idx = self._index_key(layer, h, cos, sin)
        if s <= c.index_topk:
            return (*self._attn_expanded(layer, h, cos, sin), k_idx)
        nope = c.qk_nope_head_dim
        c_q, q = self._q_latent(layer, h)
        q_idx, w = self._index_query(layer, h, c_q, cos, sin)
        with R.region(R.ATTN_IN):
            q = jnp.concatenate(
                [q[..., :nope], apply_rope_cached(q[..., nope:], cos, sin)],
                axis=-1)
        c_kv, k_rope = self._latent(layer, h, cos, sin)
        with R.region(R.ATTN_IN):
            kv = jnp.einsum("bsc,chd->bshd", c_kv, self._wkv_b(layer))
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(
                    k_rope[:, :, None, :], (1, s, c.n_heads,
                                            c.qk_rope_head_dim))], axis=-1)
            qt, kt, vt = (a[0].transpose(1, 0, 2)
                          for a in (q, k, kv[..., nope:]))
        with R.region(R.ATTN_INDEX):
            keep = _sparse.prefill_keep_mask(q_idx[0], w[0], k_idx[0],
                                             c.index_topk)
        with R.region(R.ATTN_CORE):
            out = _sparse.masked_flash_attention(
                qt, kt, vt, keep, 1.0 / math.sqrt(c.qk_head_dim))
        with R.region(R.ATTN_OUT):
            out = out.transpose(1, 0, 2)[None]
        return self._gated(layer, h, out), c_kv, k_rope, k_idx

    def _attn_selected_step(self, layer: Params, h, cos, sin, pool,
                            idx_pool, row: int, wr_page, wr_slot,
                            page_tables, lengths, run: int, fixed: int):
        """One decode position a lane: both pools get this position's row,
        the indexer scores the lane's positions, and the absorbed form
        reads the chosen rows. Tables that cannot pass `index_topk` are
        `_attn_absorbed`'s; `run`, `fixed`: the runs the tables are laid in
        behind their fixed entries (`Walk.run`, `Walk.fixed`). h (B, e).
        Returns (attention output before W_o (B, heads * v), pool,
        idx_pool, the layer's `DSA_COUNTS`)."""
        c = self.config
        ad = c.activation_dtype
        with R.region(R.ATTN_INDEX):
            idx_pool = idx_pool.at[row, wr_page, wr_slot].set(
                self._index_key(layer, h, cos, sin).astype(idx_pool.dtype),
                mode="drop")
            seen = jnp.sum(lengths)
        span = page_tables.shape[1] * pool.shape[2]
        if span <= c.index_topk:
            out, pool = self._attn_absorbed(
                layer, h, cos, sin, pool, row, wr_page, wr_slot,
                page_tables, lengths, run, fixed)
            return out, pool, idx_pool, (jnp.int32(0), seen, jnp.int32(0))
        nope, latent = c.qk_nope_head_dim, c.kv_lora_rank
        pad = c.row_width - latent - c.qk_rope_head_dim
        c_q, q = self._q_latent(layer, h)
        q_idx, w = self._index_query(layer, h, c_q, cos, sin)
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
        kernel = self._step_kernels(pool.shape[2], page_tables.shape[1],
                                    pool.dtype, fixed)
        with R.region(R.ATTN_INDEX):
            choice, chosen = _sparse.choose_paged(
                q_idx.astype(idx_pool.dtype), w, idx_pool, row, page_tables,
                lengths, c.index_topk, kernel, run=run, fixed=fixed)
            counts = (seen, jnp.sum(chosen).astype(jnp.int32),
                      jnp.sum(lengths > c.index_topk).astype(jnp.int32))
        with R.region(R.ATTN_CORE):
            o_lat = _sparse.attend_chosen(
                q_row, pool, row, page_tables, lengths, choice, latent,
                1.0 / math.sqrt(c.qk_head_dim), kernel, run=run,
                fixed=fixed)
        with R.region(R.ATTN_OUT):
            out = jnp.einsum("bhc,chv->bhv", o_lat.astype(ad),
                             w_kvb[..., nope:])
        return self._gated(layer, h, out), pool, idx_pool, counts

    # ------------------------------------------------------- forwards
    def hidden(self, layer: Params, h, at: Walk):
        """One sequence at a time past `index_topk` (each has its own
        sets)."""
        if h.shape[1] <= self.config.index_topk:
            return super().hidden(layer, h, at)
        cos, sin = at.tables[self]
        attn = jnp.concatenate([
            self._attn_selected(layer, h[i:i + 1], cos[i:i + 1],
                                sin[i:i + 1])[0]
            for i in range(h.shape[0])])
        with R.region(R.ATTN_OUT):
            return attn @ layer["wo"].astype(self.dtype)

    def _prompt(self, layer: Params, h, pools: Cache, li: int, at: Walk):
        """Each query's attention over its set, and the index keys written
        under the latent rows' page ids."""
        attn, c_kv, k_rope, k_idx = self._attn_selected(layer, h,
                                                        *at.tables[self])
        ids, page_size = at.pages[PAGED], pools["kv"].shape[2]
        return attn, {
            "kv": self._write_pages(pools["kv"], li, c_kv[0], k_rope[0],
                                    ids, page_size),
            "idx": self._write_index_pages(pools["idx"], li, k_idx[0], ids,
                                           page_size)}

    def _lanes(self, layer: Params, h, pools: Cache, li: int, at: Walk):
        """The attention over the indexer's choice; what the indexer did
        added to the step's counts."""
        page, tables = at.pages[PAGED]
        out, pool, idx_pool, counts = self._attn_selected_step(
            layer, h, *at.tables[self], pools["kv"], pools["idx"], li, page,
            at.offset, tables, at.lengths, at.run, at.fixed)
        with R.region(R.ATTN_INDEX):
            dsa = {name: pools["dsa_step"][name] + n
                   for name, n in zip(DSA_COUNTS, counts)}
        return out, {"kv": pool, "idx": idx_pool, "dsa_step": dsa}

    # ------------------------------------------------ what an engine asks
    def walk_block_pages(self, page_size: int, max_pages: int) -> int:
        """Pages a block of the walk over the latent pool holds:
        `LatentAttention`'s kernel's where the tables cannot pass
        `index_topk` (asked by one pool row's page, not by both pools'),
        else `dsa_paged_attend`'s."""
        c = self.config
        if c.max_seq_len > c.index_topk:
            return min(_sparse.ATTEND_WALK_PAGES, max_pages)
        return _paged.walk_block_pages(
            self.pools[0].bytes(self.dtype, page_size), page_size, max_pages)

    def page_run(self, page_size: int, max_pages: int,
                 fixed: int = 0) -> int:
        """Pages one copy of the step's two walks brings, which the
        allocator is asked to hand out behind one another: by what a page
        of index keys, the smaller pool's, weighs a layer
        (`ops.sparse_attention.walk_run_pages`); 1 where a step traced
        here runs no walk kernel; `LatentAttention`'s answer where the
        context cannot pass `index_topk`."""
        c = self.config
        if c.max_seq_len <= c.index_topk:
            return super().page_run(page_size, max_pages, fixed)
        if not self._step_kernels(page_size, max_pages, self.dtype, fixed):
            return 1
        return _sparse.walk_run_pages(
            self.pools[1].bytes(self.dtype, page_size), max_pages, fixed)

    def decode_kernel(self, page_size: int, dtype) -> str:
        """`LatentAttention`'s answer where the context cannot pass
        `index_topk`, else the walk over every live row that keeps the
        chosen ones, or "einsum" (the chosen rows gathered)."""
        c = self.config
        if c.max_seq_len <= c.index_topk:
            return super().decode_kernel(page_size, dtype)
        if self._step_kernels(page_size, -(-c.max_seq_len // page_size),
                              dtype):
            return _sparse.KERNEL_PAGED_ATTEND
        return "einsum"

    def _step_kernels(self, page_size: int, max_pages: int, dtype,
                      fixed: int = 0) -> bool:
        """Whether a step past `index_topk` traced here runs the two walk
        kernels (else the gathers)."""
        c = self.config
        return _sparse.step_uses_kernels(
            c.index_head_dim, c.row_width, c.kv_lora_rank, page_size,
            max_pages, dtype, fixed)


class SparseMLAMoE(MLAMoE):
    """Functional model bundle for one SparseMLAMoEConfig: `init`, `apply`
    / `loss`, and what a serving engine asks a model for
    (`models.paged.PagedDecoder`)."""

    no_mesh = "experts, the latent cache and the index keys are not " \
              "sharded over chips yet"
    step_count_names = STEP_COUNTS
    attention_type = SparseLatentAttention

    def _experts_held(self) -> int:
        return self.config.held[1]

    # ------------------------------------------------------------ init
    def layer_shapes(self, i: int) -> Dict[str, Tuple[tuple, float]]:
        """`MLAMoE`'s leaves, the routed experts' for those held here, and
        the indexer's behind them."""
        shapes = dict(super().layer_shapes(i))
        E = self.config.held[1]
        for name in ("moe_gate", "moe_up", "moe_down"):
            if name in shapes:
                (_, *rest), leaf_std = shapes[name]
                shapes[name] = ((E, *rest), leaf_std)
        return {**shapes, **self.attention.index_shapes(0.02)}

    # --------------------------------------------------------- pieces
    def _routing(self, layer: Params):
        bias, how = super()._routing(layer)
        return bias, {**how, "held": self.config.held}

    def _ffn(self, layer: Params, x, valid=None):
        """`DenseOrRoutedFFN._ffn`, an expert layer's tokens `FFN_ROWS` at
        a time where there are more (a long prefill)."""
        T, e = x.shape
        if "router" not in layer or T <= FFN_ROWS or T % FFN_ROWS:
            return super()._ffn(layer, x, valid)
        if valid is None:
            valid = jnp.ones((T,), bool)
        whole = super()._ffn
        y, counts = jax.lax.map(
            lambda xv: whole(layer, *xv),
            (x.reshape(-1, FFN_ROWS, e), valid.reshape(-1, FFN_ROWS)))
        with R.region(R.MOE_ROUTE):
            return y.reshape(T, e), jax.tree.map(
                lambda n: jnp.sum(n, axis=0), counts)

    # what `benchmarks/tools/dsa_sets.py` reads off the model: the
    # indexer's pieces, its mixer's
    def _q_latent(self, layer: Params, h):
        return self.attention._q_latent(layer, h)

    def _index_key(self, layer: Params, h, cos, sin):
        return self.attention._index_key(layer, h, cos, sin)

    def _index_query(self, layer: Params, h, c_q, cos, sin):
        return self.attention._index_query(layer, h, c_q, cos, sin)

    # ------------------------------------------------ what an engine asks
    def index_page_bytes(self, page_size: int, dtype=None) -> int:
        """What of `cache_page_bytes` is the index keys'."""
        return self._pool_bytes((PAGED,), dtype, page_size, names=("idx",))
