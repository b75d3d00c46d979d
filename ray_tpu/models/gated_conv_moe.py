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
causal, zeros before the sequence, **no activation** (`ops.conv.
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
fixed class (the tails are a `paged.Pool` of kind SLOT): it names the slot of its tails (a slot
holds a tail alone: there is no `"state"`, and a prefill scans nothing)
and is, like every later entry, a page of its keys and values.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import regions as R
from ray_tpu.models.config import ConfigDtypes
from ray_tpu.models.gqa import Attention
from ray_tpu.models.moe import STEP_COUNTS, DenseOrRoutedFFN
from ray_tpu.models.paged import (SLOT, Cache, ExpertCounts, Layer, Mixer,
                                  PagedDecoder, Params, Pool, Walk,
                                  write_slot)
from ray_tpu.ops import rope as _rope
from ray_tpu.ops.conv import causal_conv, conv_tail_step, tail_shape
from ray_tpu.ops.norms import rms_norm_reference

CONV, ATTENTION = "conv", "full_attention"


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


class HeadNormAttention(Attention):
    """The attention layers' mixer: q and k normed a head (one weight of
    `head_dim` shared by the heads), then rotated over the whole head."""

    def __init__(self, config: GatedConvMoEConfig):
        c = self.config = config
        super().__init__(c.d_model, c.n_heads, c.n_kv_heads, c.head_dim,
                         c.activation_dtype)

    def shapes(self, std: float, out_std: float) -> Dict[str, tuple]:
        return {**super().shapes(std, out_std),
                "q_norm": ((self.head_dim,), 0.0),
                "k_norm": ((self.head_dim,), 0.0)}

    @R.region(R.ATTN_IN)
    def _qkv(self, layer: Params, h, at: Walk):
        c = self.config
        positions = at.positions_along(h)
        q, k, v = super()._qkv(layer, h, at)
        cos, sin = _rope.rope_cos_sin(positions, c.head_dim, c.rope_theta)
        q = rms_norm_reference(q, layer["q_norm"], c.norm_eps)
        k = rms_norm_reference(k, layer["k_norm"], c.norm_eps)
        return (_rope.apply_rope_cached(q, cos, sin),
                _rope.apply_rope_cached(k, cos, sin), v)


class GatedConv(Mixer):
    """The gated short convolution over `d_model` channels, `width` taps
    and no bias. All it keeps of a sequence is the convolution's tail: a
    slot holds no state, and a prefill scans nothing."""

    def __init__(self, d_model: int, width: int, dtype):
        self.d_model, self.width, self.dtype = d_model, width, dtype
        self.pools = (Pool("tail", SLOT, tail_shape(width, d_model)),)

    def shapes(self, std: float, out_std: float) -> Dict[str, tuple]:
        e = self.d_model
        return {"w_in": ((e, 3 * e), std), "conv": ((self.width, e), std),
                "w_out": ((e, e), out_std)}

    def decode_kernel(self, page_size: int, dtype) -> None:
        """None: the convolution's step is plain `jax.numpy`."""

    @R.region(R.MIXER_IN)
    def _conv_in(self, layer: Params, h):
        """h (..., e) normed -> (g = B * u: what the convolution runs over,
        C: what multiplies its result), of `[B | C | u] = h W_in`."""
        B, C, u = jnp.split(h @ layer["w_in"].astype(self.dtype), 3,
                            axis=-1)
        return B * u, C

    @R.region(R.MIXER_OUT)
    def _conv_out(self, layer: Params, C, conv):
        return (C * conv) @ layer["w_out"].astype(self.dtype)

    def _seq(self, layer: Params, h, true_len=None):
        """The gated convolution over one sequence h (s, e). Returns (the
        output after W_out, the last `width - 1` rows of g before
        `true_len`: the tail)."""
        g, C = self._conv_in(layer, h)
        with R.region(R.MIXER_CORE):
            conv, tail = causal_conv(g, layer["conv"], true_len,
                                     activate=False)
        return self._conv_out(layer, C, conv), tail

    def hidden(self, layer: Params, h, at: Walk):
        return jax.vmap(lambda seq: self._seq(layer, seq)[0])(h)

    def prefill(self, layer: Params, h, pools: Cache, li: int, at: Walk):
        out, tail = self._seq(layer, h, at.true_len)
        return out, write_slot(pools, li, at.slot, None, tail)

    def decode_step(self, layer: Params, h, pools: Cache, li: int,
                    at: Walk):
        g, C = self._conv_in(layer, h)
        with R.region(R.MIXER_CORE):
            conv, tail = conv_tail_step(g, layer["conv"], pools["tail"], li,
                                        at.slot, activate=False)
        return self._conv_out(layer, C, conv), {"tail": tail}


class GatedConvMoE(DenseOrRoutedFFN, ExpertCounts, PagedDecoder):
    """Functional model bundle for one GatedConvMoEConfig: `init`, `apply`
    / `loss` (a plain forward, differentiated by JAX), and what a serving
    engine asks a model for (`models.paged.PagedDecoder`)."""

    no_mesh = ("neither the tails' pool nor the experts are sharded over "
               "chips yet")
    # logits = N_f(x) E^T: the family ties its head to the embedding
    tied_head = True
    # a layer holds all its experts: none is away, no slot computes nothing
    step_count_names = STEP_COUNTS[:3]

    def __init__(self, config: GatedConvMoEConfig, mesh=None):
        super().__init__(config, mesh)
        c = config
        self.attention = HeadNormAttention(c)
        self.conv = GatedConv(c.d_model, c.conv_width, c.activation_dtype)
        mixers = {ATTENTION: self.attention, CONV: self.conv}
        self._lay([self.attention, self.conv], [
            Layer((mixers[kind],), "norm",
                  experts=c.num_experts if i in c.expert_layers else 0)
            for i, kind in enumerate(c.layer_types)])

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
        mixer, = self.layers[i].mixers
        shapes = {"norm": ((e,), 0.0), **mixer.shapes(std, out_std),
                  "mlp_norm": ((e,), 0.0)}
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
    def _routing(self, layer: Params):
        c = self.config
        bias = (layer["router_bias"] if c.use_expert_bias
                else jnp.zeros((c.num_experts,), jnp.float32))
        return bias, dict(top_k=c.num_experts_per_tok,
                          norm_topk_prob=c.norm_topk_prob,
                          scale=c.routed_scaling_factor)
