"""A decoder of double layers with a shortcut expert branch: two latent
attentions and two dense feed-forwards in line, and beside them a routed
feed-forward that reads the first half's normed stream and joins at the
layer's end (the shortcut-connected mixture of experts of the
LongCat-Flash family), on the ops `MLAMoE` runs on and behind the same
serving engine.

A layer, `x` its input and every `N` an RMSNorm with its own weight:

    h  = x + MLA_0(N(x))
    u  = N(h)
    m  = MoE(u)                      # the shortcut branch: joins at the end
    h  = h + FFN_0(u)                # dense SwiGLU
    h  = h + MLA_1(N(h))
    h  = h + FFN_1(N(h))
    x' = h + m

Nothing orders `m` against the second half but the data: no barrier is put
between them, and the compiler places the branch where it likes.

`MLA` is the mixer `models.latent.LatentAttention`, with the two LoRA scales this
family has: `mla_scale_q_lora` multiplies the normed query latent by
`sqrt(d_model / q_lora_rank)` **before `W_qb`** (1,536 numbers a token
where the heads are 12,288), and `mla_scale_kv_lora` the normed key-value
latent by `sqrt(d_model / kv_lora_rank)` **in the row the cache holds**:
scaled once, in float32, when the row is written, so `k_nope` and `v`
carry it and `k_rope` does not, and neither `W_kvb` nor a decode step
multiplies by it again.

`MoE` is `models.moe.dropless_moe_ffn`: a softmax in float32 over
`n_routed_experts + zero_expert_num` slots, the top `num_experts_per_tok`
of `score + bias` chosen, weights `routed_scaling_factor x score` and not
renormalised; the last `zero_expert_num` slots compute nothing (a pair
adds `w u`). **The layer is told which experts it holds**
(`experts_held = (first, count)` of `n_routed_experts`): it routes over all
slots, computes its own experts' rows, adds the identity part of every
token and leaves out what the experts held elsewhere would add, which is
one chip's part of a layer divided over chips, without the exchange.
No shared expert, no leading dense layer.

**The cache**: a layer owns two rows of the latent pool, `(2 x layers,
pages, page_size, row_width)` under `"kv"`, row `2 i + j` attention `j` of
layer `i`; both are written for every position, so a page costs twice what
`MLAMoE`'s costs a layer. Beside it `paged.ExpertCounts`' two entries,
`"moe_step"` all of `moe.STEP_COUNTS`: `moe_pairs` (rows given to held
experts), `moe_experts_touched`, `moe_load_max`, `moe_zero_pairs` (choices
of a slot that computes nothing) and `moe_away_pairs` (choices of an
expert held elsewhere).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import regions as R
from ray_tpu.models.latent import LatentAttention, LatentDims
from ray_tpu.models.moe import SCORING, dropless_moe_ffn, swiglu
from ray_tpu.models.paged import (PAGED, Cache, ExpertCounts, Layer,
                                  PagedDecoder, Params, decode_lanes,
                                  prefill_page_ids)

@dataclasses.dataclass(frozen=True)
class ShortcutMLAMoEConfig(LatentDims):
    """Fields under the published keys' meanings (`config.json` of
    LongCat-Flash); `n_layers` counts double layers, `n_routed_experts`
    the experts of the whole layer and `experts_held` this chip's."""
    vocab_size: int = 131072
    d_model: int = 6144                     # hidden_size
    n_layers: int = 28                      # num_layers (double layers)
    n_heads: int = 64                       # num_attention_heads
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 12288                       # ffn_hidden_size (dense)
    moe_intermediate_size: int = 2048       # expert_ffn_hidden_size
    n_routed_experts: int = 512
    zero_expert_num: int = 256              # slots that compute nothing
    experts_held: Optional[Tuple[int, int]] = None   # (first, count); all
    num_experts_per_tok: int = 12           # moe_topk
    routed_scaling_factor: float = 6.0
    norm_topk_prob: bool = False
    scoring_func: str = "softmax"
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    max_seq_len: int = 4096
    rope_theta: float = 10000000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.scoring_func not in SCORING:
            raise ValueError(f"scoring {self.scoring_func!r}: the router "
                             f"scores by one of {sorted(SCORING)}")
        first, count = self.held
        if count < 1 or not 0 <= first <= self.n_routed_experts - count:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.n_routed_experts} experts")

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the experts this chip holds."""
        return tuple(self.experts_held or (0, self.n_routed_experts))

    @property
    def router_slots(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def q_lora_scale(self) -> float:
        return (math.sqrt(self.d_model / self.q_lora_rank)
                if self.mla_scale_q_lora else 1.0)

    @property
    def kv_lora_scale(self) -> float:
        return (math.sqrt(self.d_model / self.kv_lora_rank)
                if self.mla_scale_kv_lora else 1.0)


def tiny_shortcut_mla_moe(vocab_size: int = 256,
                          experts_held=(4, 4)) -> ShortcutMLAMoEConfig:
    """CI/debug model: every mechanism at a size the CPU runs in seconds
    (values narrower than keys, a share of the experts that does not start
    at 0, slots that compute nothing, both scales other than 1)."""
    return ShortcutMLAMoEConfig(
        vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
        q_lora_rank=32, kv_lora_rank=96, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=8, d_ff=128,
        moe_intermediate_size=32, n_routed_experts=16, zero_expert_num=8,
        experts_held=experts_held, num_experts_per_tok=4, max_seq_len=128,
        dtype="float32", param_dtype="float32")


class ShortcutMLAMoE(ExpertCounts, PagedDecoder):
    """Functional model bundle for one ShortcutMLAMoEConfig: `init`,
    `apply` / `loss` (training graph), and what a serving engine asks a
    model for (`models.paged.PagedDecoder`). Its table says what a layer
    keeps (two rows of the latent pool, the experts held); the layer
    itself, with the expert branch beside its second half, is no row of
    mixers and a feed-forward, so `_layer` and the three walks are its
    own."""

    no_mesh = "the experts' exchange over chips has not been built"

    def __init__(self, config: ShortcutMLAMoEConfig, mesh=None):
        super().__init__(config, mesh)
        self.attention = LatentAttention(config)
        self._lay([self.attention], [Layer(
            (self.attention,) * 2, experts=config.held[1])
        ] * config.n_layers)

    # ------------------------------------------------------------ init
    def layer_shapes(self, i: int) -> Dict[str, Any]:
        """A double layer's leaves, every layer alike (a norm's scale is
        stored as w, the layer multiplying by 1 + w), named in sorted
        order at every level: the order in which `init` has dealt a layer
        its random keys since the class was written (it flattened them)."""
        c = self.config
        e, f, E = c.d_model, c.moe_intermediate_size, c.held[1]
        std = 0.02
        out_std = std / math.sqrt(4 * c.n_layers)
        ffn = {"down": ((c.d_ff, e), out_std), "gate": ((e, c.d_ff), std),
               "mlp_norm": ((e,), 0.0), "up": ((e, c.d_ff), std)}
        attn = dict(sorted({"attn_norm": ((e,), 0.0),
                            **self.attention.shapes(std, out_std)}.items()))
        return {
            "attn": [dict(attn) for _ in range(2)],
            "ffn": [dict(ffn) for _ in range(2)],
            "moe_down": ((E, f, e), out_std),
            "moe_gate": ((E, e, f), std), "moe_up": ((E, e, f), std),
            "router": ((e, c.router_slots), std),
            "router_bias": ((c.router_slots,), 0.0)}

    # --------------------------------------------------------- pieces
    @staticmethod
    def _dense(ffn: Params, u):
        """SwiGLU of one half on the normed stream u (..., e)."""
        return swiglu(u, ffn["gate"], ffn["up"], ffn["down"])

    def _experts(self, layer: Params, u, valid=None):
        """The shortcut branch on the normed stream u (..., e): this
        chip's experts' part and the identity part. Returns (m, counts)."""
        c = self.config
        m, counts = dropless_moe_ffn(
            u.reshape(-1, u.shape[-1]), layer["router"],
            layer["router_bias"], layer["moe_gate"], layer["moe_up"],
            layer["moe_down"], top_k=c.num_experts_per_tok,
            norm_topk_prob=c.norm_topk_prob, scale=c.routed_scaling_factor,
            valid=None if valid is None else valid.reshape(-1),
            scoring=c.scoring_func, zero_experts=c.zero_expert_num,
            held=c.held)
        return m.reshape(u.shape), counts

    def _layer(self, layer: Params, x, attend, valid=None):
        """One double layer on the stream x (..., e); `attend(j, h)` is
        attention `j` of the layer on its normed input, before `W_o`.
        Returns (x', the expert branch's counts)."""
        ad = self.config.activation_dtype
        a0, a1 = layer["attn"]
        f0, f1 = layer["ffn"]
        out = attend(0, self._norm(x, a0["attn_norm"]))
        with R.region(R.ATTN_OUT):
            h = x + out @ a0["wo"].astype(ad)
        u = self._norm(h, f0["mlp_norm"])
        m, counts = self._experts(layer, u, valid)
        with R.region(R.FFN):
            h = h + self._dense(f0, u)
        out = attend(1, self._norm(h, a1["attn_norm"]))
        with R.region(R.ATTN_OUT):
            h = h + out @ a1["wo"].astype(ad)
        u = self._norm(h, f1["mlp_norm"])
        with R.region(R.FFN):
            h = h + self._dense(f1, u)
        with R.region(R.MOE_EXPERTS):       # the shortcut branch joins
            return h + m, counts

    # --------------------------------------------------------- forward
    def hidden(self, params: Params, tokens: jax.Array) -> jax.Array:
        """tokens (b, s) -> hidden states after the final norm."""
        b, s = tokens.shape
        x = self._embed(params, tokens)
        at = self._open(lambda: jnp.broadcast_to(jnp.arange(s), (b, s)))
        cos, sin = at.tables[self.attention]
        for layer in params["layers"]:
            x, _ = self._layer(
                layer, x, lambda j, h, layer=layer:
                self.attention._attn_expanded(layer["attn"][j], h, cos,
                                              sin)[0])
        return self._final_norm(params, x)

    def prefill(self, params: Params, tokens: jax.Array, true_len,
                page_table: jax.Array, cache: Cache,
                page_size: int) -> Tuple[jax.Array, Cache]:
        """As `PagedDecoder.prefill`: the expanded attention, both of a
        layer's pool rows written as whole pages in place. Padding past
        `true_len` is given to no expert and adds no identity part."""
        pools = dict(cache)
        s = tokens.shape[0]
        x = self._embed(params, tokens)[None]                   # (1, s, e)
        at = self._open(lambda: jnp.arange(s)[None], true_len=true_len)
        with R.region(R.CACHE):
            valid = (jnp.arange(s) < true_len)[None]
        at.pages[PAGED] = prefill_page_ids(
            page_table, true_len, s, pools["kv"].shape[1], page_size)
        for row, layer in zip(self.layers, params["layers"]):
            def attend(j, h):
                out, written = self.attention._prompt(
                    layer["attn"][j], h, pools, row.rows[j], at)
                pools.update(written)
                return out
            x, _ = self._layer(layer, x, attend, valid)
        return self._logits(params, x, true_len), pools

    def decode_step(self, params: Params, cache: Cache, tokens: jax.Array,
                    positions: jax.Array, page_tables: jax.Array,
                    active: jax.Array,
                    page_size: int) -> Tuple[jax.Array, Cache]:
        """As `PagedDecoder.decode_step`, both attentions in the absorbed
        form. Inactive lanes write nothing, are given to no expert and add
        no identity part."""
        pools = dict(cache)
        x = self._embed(params, tokens)                         # (B, e)
        at = self._open(lanes=positions)
        page, at.offset, at.lengths = decode_lanes(
            positions, page_tables, active, pools["kv"].shape[1], page_size)
        at.pages[PAGED] = page, page_tables
        at.run = self.page_run(page_size, page_tables.shape[1])
        load, sums = pools["moe_load"], self._step_sums()
        for row, layer in zip(self.layers, params["layers"]):
            def attend(j, h):
                out, written = self.attention._lanes(
                    layer["attn"][j], h, pools, row.rows[j], at)
                pools.update(written)
                return out
            x, counts = self._layer(layer, x, attend, active)
            with R.region(R.MOE_ROUTE):
                load = load.at[row.expert_row].add(counts["load"])
            sums = self._count_step(sums, counts)
        return self._logits(params, x), {**pools,
                                         **self._counted(load, sums)}
