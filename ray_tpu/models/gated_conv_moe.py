"""A decoder whose mixers are gated short convolutions, with grouped-query
attention in one layer of four, over dense or routed feed-forwards and a
tied head (the `lfm2_moe` family), on the ops the other classes run on and
behind the same serving engine.

With `N_1`, `N_2`, `N_f` RMSNorms and `E` the embedding's table:

    x_0 = E[token]
    x = x + Op_l(N_1(x))      Op_l = Conv where layer_types[l] == "conv",
                                     Attn where "full_attention"
    x = x + FF_l(N_2(x))      FF_l = MLP where l < num_dense_layers, else MoE
    logits = N_f(x) E^T

`Conv(h)`: `[B | C | u] = h W_in` (three thirds of `d_model`); `g = B * u`;
`c_t = sum_i w_i g_{t - width + 1 + i}`, depthwise over the channels,
causal, zeros before the sequence, **no activation** (`ops.gated_delta.
causal_conv(activate=False)`: the convolution is the mixer, between two
gates, where the recurrent classes' is a scan's way in); `out = (C * c)
W_out`. What a decode step continues from is `g`'s last `width - 1` rows:
the layer's whole state.

`Attn(h)`: `q = h W_q`, `k = h W_k`, `v = h W_v`; q and k normed **a head**
(an RMSNorm over a head's numbers, one weight of `head_dim` shared by the
heads) and then rotated over the whole head (`rope_theta`, split halves);
causal softmax of `q . k / sqrt(head_dim)`; `W_o`. The key is normed and
rotated before it is written to its page.

`MoE(h)`: `models.moe.dropless_moe_ffn` over all the layer's experts and
no shared one: sigmoid scores, the choice by score + `router_bias` where
`use_expert_bias`, the weights the scores themselves, renormalised where
`norm_topk_prob`, times `routed_scaling_factor`.

**The cache**: pools `"k"`, `"v"` `(attention layers, num_pages, page, kv
heads x head dim)` (`models/gqa.py`; a head of 64 is half a 128-lane of a
row: `ops.paged_attention.LANE`) and `"tail"` `(conv layers, slots + 1,
*tail_shape)`. A sequence's first table entry is a page of the allocator's
fixed class (`paged.StateSlots`): it names the slot of its tails (a slot
holds a tail alone: there is no `"state"`, and a prefill scans nothing)
and is, like every later entry, a page of its keys and values.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import gqa
from ray_tpu.models import regions as R
from ray_tpu.models.config import ConfigDtypes
from ray_tpu.models.moe import STEP_COUNTS, DenseOrRoutedFFN
from ray_tpu.models.paged import (Cache, ExpertCounts, PagedDecoder, Params,
                                  StateSlots, decode_lanes,
                                  decode_state_slots, prefill_page_ids,
                                  prefill_state_slot)
from ray_tpu.ops import gated_delta as _gd
from ray_tpu.ops import paged_attention as _paged
from ray_tpu.ops import rope as _rope
from ray_tpu.ops.norms import rms_norm_reference

CONV, ATTENTION = "conv", "full_attention"
# the region of a mixer's residual addition, by the layer's kind
_CLOSES = {CONV: R.MIXER_OUT, ATTENTION: R.ATTN_OUT}


@dataclasses.dataclass(frozen=True)
class GatedConvMoEConfig(ConfigDtypes):
    """Fields under the published keys' meanings (`config.json` of
    `lfm2_moe`); `layer_types` names every layer."""
    vocab_size: int = 65536
    d_model: int = 2048                     # hidden_size
    layer_types: Tuple[str, ...] = (CONV, CONV, ATTENTION, CONV) * 5 + (
        CONV, ATTENTION, CONV, CONV)
    n_heads: int = 32                       # num_attention_heads
    n_kv_heads: int = 8                     # num_key_value_heads
    head_dim: int = 64                      # hidden_size / heads
    rope_theta: float = 1000000.0
    conv_width: int = 3                     # conv_L_cache; no bias
    d_ff: int = 7168                        # intermediate_size (dense)
    moe_intermediate_size: int = 1792
    num_experts: int = 32
    num_experts_per_tok: int = 4
    num_dense_layers: int = 2
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    max_seq_len: int = 4096
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if set(self.layer_types) - {CONV, ATTENTION}:
            raise ValueError(f"layer kinds {set(self.layer_types)} not "
                             f"built")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("kv heads must divide the heads")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def of_kind(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == kind)

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(range(min(self.num_dense_layers, self.n_layers),
                           self.n_layers))

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


def tiny_gated_conv_moe(vocab_size: int = 256) -> GatedConvMoEConfig:
    """CI/debug model: every mechanism at a size the CPU runs in seconds
    (both kinds of mixer, 8 query heads of 64 over 2 kv heads: a group of
    4, a head half a 128-lane, so the paged kernel tiles under the
    interpreter; one dense layer, then 8 experts top-2 under a bias)."""
    return GatedConvMoEConfig(
        vocab_size=vocab_size, d_model=64,
        layer_types=(CONV, ATTENTION, CONV, CONV), n_heads=8, n_kv_heads=2,
        head_dim=64, rope_theta=1e4, d_ff=128, moe_intermediate_size=32,
        num_experts=8, num_experts_per_tok=2, num_dense_layers=1,
        max_seq_len=256, dtype="float32", param_dtype="float32")


class GatedConvMoE(DenseOrRoutedFFN, StateSlots, ExpertCounts, PagedDecoder):
    """Functional model bundle for one GatedConvMoEConfig: `init`, `apply`
    / `loss` (a plain forward, differentiated by JAX), and what a serving
    engine asks a model for (`models.paged.PagedDecoder`)."""

    no_mesh = ("neither the tails' pool nor the experts are sharded over "
               "chips yet")
    # logits = N_f(x) E^T: the family ties its head to the embedding
    tied_head = True
    # a layer holds all its experts: none is away, no slot computes nothing
    step_count_names = STEP_COUNTS[:3]

    # ------------------------------------------------------------ init
    def layer_shapes(self, i: int) -> Dict[str, Tuple[tuple, float]]:
        """A mixer by `layer_types[i]` (the gated convolution, or attention
        with its two head norms), a feed-forward by `num_dense_layers` (a
        SwiGLU, or router, bias and experts), two norms. Zeros are a norm's
        scale w, the layer multiplying by 1 + w, and the router's bias."""
        c = self.config
        e = c.d_model
        std = 0.02
        out_std = std / math.sqrt(2 * c.n_layers)
        if c.layer_types[i] == ATTENTION:
            q = c.n_heads * c.head_dim
            shapes = {"norm": ((e,), 0.0), "wq": ((e, q), std),
                      "wk": ((e, c.kv_dim), std), "wv": ((e, c.kv_dim), std),
                      "wo": ((q, e), out_std),
                      "q_norm": ((c.head_dim,), 0.0),
                      "k_norm": ((c.head_dim,), 0.0)}
        else:
            shapes = {"norm": ((e,), 0.0), "w_in": ((e, 3 * e), std),
                      "conv": ((c.conv_width, e), std),
                      "w_out": ((e, e), out_std)}
        shapes["mlp_norm"] = ((e,), 0.0)
        if i < c.num_dense_layers:
            shapes.update(gate=((e, c.d_ff), std), up=((e, c.d_ff), std),
                          down=((c.d_ff, e), out_std))
            return shapes
        E, f = c.num_experts, c.moe_intermediate_size
        shapes.update(router=((e, E), std), router_bias=((E,), 0.0),
                      moe_gate=((E, e, f), std), moe_up=((E, e, f), std),
                      moe_down=((E, f, e), out_std))
        return shapes

    # --------------------------------------------------------- pieces
    @R.region(R.ATTN_IN)
    def _qkv(self, layer: Params, h, positions):
        """h (..., e) normed, `positions` (...) -> q (..., heads, hd), k, v
        (..., kv heads, hd): q and k normed a head, then rotated."""
        c = self.config
        q, k, v = gqa.qkv(layer, h, c.n_heads, c.n_kv_heads, c.head_dim,
                          c.activation_dtype)
        cos, sin = _rope.rope_cos_sin(positions, c.head_dim, c.rope_theta)
        q = rms_norm_reference(q, layer["q_norm"], c.norm_eps)
        k = rms_norm_reference(k, layer["k_norm"], c.norm_eps)
        return (_rope.apply_rope_cached(q, cos, sin),
                _rope.apply_rope_cached(k, cos, sin), v)

    def _attn_seq(self, layer: Params, h):
        """Causal attention over whole sequences h (b, s, e). Returns (the
        output after W_o, k, v (b, s, kv heads, hd))."""
        q, k, v = self._qkv(layer, h, jnp.arange(h.shape[-2]))
        out = gqa.attend_seq(q, k, v)
        with R.region(R.ATTN_OUT):
            out = out.reshape(*h.shape[:-1], -1)
            return out @ layer["wo"].astype(
                self.config.activation_dtype), k, v

    @R.region(R.MIXER_IN)
    def _conv_in(self, layer: Params, h):
        """h (..., e) normed -> (g = B * u: what the convolution runs over,
        C: what multiplies its result), of `[B | C | u] = h W_in`."""
        B, C, u = jnp.split(
            h @ layer["w_in"].astype(self.config.activation_dtype), 3,
            axis=-1)
        return B * u, C

    @R.region(R.MIXER_OUT)
    def _conv_out(self, layer: Params, C, conv):
        return (C * conv) @ layer["w_out"].astype(
            self.config.activation_dtype)

    def _conv_seq(self, layer: Params, h, true_len=None):
        """The gated convolution over one sequence h (s, e). Returns (the
        output after W_out, the last `conv_width - 1` rows of g before
        `true_len`: the tail)."""
        g, C = self._conv_in(layer, h)
        with R.region(R.MIXER_CORE):
            conv, tail = _gd.causal_conv(g, layer["conv"], true_len,
                                         activate=False)
        return self._conv_out(layer, C, conv), tail

    def _routing(self, layer: Params):
        c = self.config
        bias = (layer["router_bias"] if c.use_expert_bias
                else jnp.zeros((c.num_experts,), jnp.float32))
        return bias, dict(top_k=c.num_experts_per_tok,
                          norm_topk_prob=c.norm_topk_prob,
                          scale=c.routed_scaling_factor)

    # --------------------------------------------------------- forward
    def hidden(self, params: Params, tokens: jax.Array) -> jax.Array:
        """tokens (b, s) -> hidden states after the final norm."""
        c = self.config
        x = self._embed(params, tokens)
        for i, layer in enumerate(params["layers"]):
            h = self._norm(x, layer["norm"])
            kind = c.layer_types[i]
            if kind == ATTENTION:
                mixed = self._attn_seq(layer, h)[0]
            else:
                mixed = jax.vmap(
                    lambda seq: self._conv_seq(layer, seq)[0])(h)
            with R.region(_CLOSES[kind]):
                x = x + mixed
            x, _ = self._block_ffn(layer, x)
        return self._final_norm(params, x)

    # ------------------------------------------------ what an engine asks
    def state_bytes(self, dtype=None) -> int:
        """Bytes the convolutions keep of one sequence, whatever its
        length: a tail a layer."""
        c = self.config
        return (len(c.of_kind(CONV)) * math.prod(
            _gd.tail_shape(c.conv_width, c.d_model))
            * jnp.dtype(dtype or c.activation_dtype).itemsize)

    def init_cache(self, num_pages: int, page_size: int, dtype=None,
                   fixed_pages: int = 0) -> Cache:
        """`num_pages` pages in the attention layers' pools; `fixed_pages`
        tail slots (the allocator's fixed class, one a sequence) and one
        more, nobody's, in the convolutions'."""
        c = self.config
        dt = dtype or c.activation_dtype
        kv = (len(c.of_kind(ATTENTION)), num_pages, page_size, c.kv_dim)
        tail = (len(c.of_kind(CONV)), fixed_pages + 1,
                *_gd.tail_shape(c.conv_width, c.d_model))
        make = jax.jit(lambda: {
            "k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
            "tail": jnp.zeros(tail, dt), **self._zero_counts()})
        return make()

    @property
    def expert_load_shape(self) -> Tuple[int, int]:
        return len(self.config.expert_layers), self.config.num_experts

    def page_bytes(self, page_size: int, tp_shards: int = 1,
                   dtype=None) -> int:
        """Keys and values of the attention layers."""
        c = self.config
        return len(c.of_kind(ATTENTION)) * gqa.layer_page_bytes(
            c.kv_dim, page_size, dtype or c.activation_dtype, tp_shards)

    def decode_attention(self, page_size: int, dtype=None) -> str:
        """The attention layers' kernel (the convolution's step is plain
        `jax.numpy`), or "einsum"."""
        c = self.config
        return gqa.decode_kernels(
            c.head_dim, page_size, dtype or c.activation_dtype,
            [(_paged.KERNEL_PAGED_DECODE, c.of_kind(ATTENTION))], c.kv_dim)

    def walk_block_pages(self, page_size: int, max_pages: int) -> int:
        """Of the attention layers' walk."""
        c = self.config
        return gqa.walk_block_pages(c.kv_dim, page_size, max_pages,
                                    c.activation_dtype)

    def prefill(self, params: Params, tokens: jax.Array, true_len,
                page_table: jax.Array, cache: Cache,
                page_size: int) -> Tuple[jax.Array, Cache]:
        """An attention layer through the flash kernel, its keys and
        values written as whole pages in place; a convolution over the
        bucket, its tail taken at `true_len` and written whole into the
        slot the table's first entry names; padding past `true_len` given
        to no expert."""
        c = self.config
        pools = dict(cache)
        num_pages, slots = pools["k"].shape[1], pools["tail"].shape[1] - 1
        s = tokens.shape[0]
        x = self._embed(params, tokens)                         # (s, e)
        ids = prefill_page_ids(page_table, true_len, s, num_pages, page_size)
        slot = prefill_state_slot(page_table, slots)
        with R.region(R.CACHE):
            valid = jnp.arange(s) < true_len
        for i, layer in enumerate(params["layers"]):
            h = self._norm(x, layer["norm"])
            kind = c.layer_types[i]
            if kind == ATTENTION:
                li = c.of_kind(ATTENTION).index(i)
                mixed, k, v = self._attn_seq(layer, h[None])
                mixed = mixed[0]
                pools.update(gqa.write_prompt(pools, ("k", "v"), li, ids,
                                              k, v))
            else:
                li = c.of_kind(CONV).index(i)
                mixed, tail = self._conv_seq(layer, h, true_len)
                pools.update(self._write_slot(pools, li, slot, None, tail))
            with R.region(_CLOSES[kind]):
                x = x + mixed
            x, _ = self._block_ffn(layer, x, valid)
        return self._logits(params, x, true_len), pools

    def decode_step(self, params: Params, cache: Cache, tokens: jax.Array,
                    positions: jax.Array, page_tables: jax.Array,
                    active: jax.Array,
                    page_size: int) -> Tuple[jax.Array, Cache]:
        """An inactive lane, or one whose table is unassigned, writes no
        page and no tail, and is given to no expert."""
        c = self.config
        ad = c.activation_dtype
        pools = dict(cache)
        num_pages, slots = pools["k"].shape[1], pools["tail"].shape[1] - 1
        B = tokens.shape[0]
        x = self._embed(params, tokens)                         # (B, e)
        page, offset, lengths = decode_lanes(positions, page_tables, active,
                                             num_pages, page_size)
        slot = decode_state_slots(page_tables, active, slots)
        load, sums = pools["moe_load"], self._step_sums()
        for i, layer in enumerate(params["layers"]):
            h = self._norm(x, layer["norm"])
            kind = c.layer_types[i]
            if kind == ATTENTION:
                li = c.of_kind(ATTENTION).index(i)
                q, k, v = self._qkv(layer, h, positions)
                out, written = gqa.decode_attend(
                    pools, ("k", "v"), li, page, offset, q, k, v,
                    page_tables, lengths)
                pools.update(written)
                with R.region(R.ATTN_OUT):
                    mixed = out.astype(ad).reshape(B, -1) @ layer[
                        "wo"].astype(ad)
            else:
                li = c.of_kind(CONV).index(i)
                g, C = self._conv_in(layer, h)
                with R.region(R.MIXER_CORE):
                    conv, pools["tail"] = _gd.conv_tail_step(
                        g, layer["conv"], pools["tail"], li, slot,
                        activate=False)
                mixed = self._conv_out(layer, C, conv)
            with R.region(_CLOSES[kind]):
                x = x + mixed
            x, counts = self._block_ffn(layer, x, active)
            if counts is not None:
                li = c.expert_layers.index(i)
                with R.region(R.MOE_ROUTE):
                    load = load.at[li].add(counts["load"])
                sums = self._count_step(sums, counts)
        return self._logits(params, x), {**pools,
                                         **self._counted(load, sums)}
