"""A GQA decoder whose layers are of two kinds of attention and two kinds
of feed-forward, named layer by layer by its config's lists: the `laguna`
family (Laguna-XS.2), on the same ops as `Transformer` and `MLAMoE` and
behind the same serving engine.

With `h = RMSNorm(x)` the normed input of layer i, of kind `layer_types[i]`
and with `n_heads_per_layer[i]` query heads over `n_kv_heads` kv heads of
`head_dim` (not `d_model / n_heads`):

    q = h W_q (heads x hd);  k = h W_k;  v = h W_v (kv heads x hd)
    q, k rotated by the kind's rotary scheme (`RopeParams`)
    scores q_i . k_j / sqrt(hd) for j <= i, on a sliding layer only for
    i - j < sliding_window; softmax; o_h = P_h v
    g = sigmoid(h W_g), one number a head;  attn = concat_h(g_h o_h) W_o

A **full** layer's rotary scheme may rotate the leading part of a head only
(`partial_rotary_factor`) at YaRN frequencies with cos and sin times an
attention factor; a **sliding** layer's is plain. Both tables of cos and
sin are made once a program. The feed-forward of layer i is a SwiGLU
(`mlp_layer_types[i] == "dense"`) or `models.moe.dropless_moe_ffn`
(sigmoid scores in float32, no correction bias, the top-k normalised and
scaled) plus one shared expert (`"sparse"`). Layers are unlike, so they
are held per layer, as `MLAMoE` holds them.

**Two kinds of cache behind one page table.** A full layer keeps every
position: pools `"k"`, `"v"` of `(full layers, num_pages, page, kv x hd)`,
logical page j of a sequence at its table's entry j. A sliding layer sees
a sequence's last `sliding_window` positions and keeps `window_pages` =
window / page + 1 pages of it for ever, in a ring: pools `"wk"`, `"wv"` of
`(sliding layers, ring_pages, page, kv x hd)`, logical page j at entry `j
mod window_pages`, what fell out of the window overwritten. The ring's
pages are the ids `0 .. ring_pages - 1`, which the full pools hold too
(`serve/llm/kv_cache.py`: the allocator's fixed class), so one table serves
both kinds and nothing is keyed by lane. `prefill` writes a full layer's
pages whole and a sliding layer's last `window_pages`; `decode_step` reads
a full layer through `ops.paged_attention.paged_decode_attention` and a
sliding one through `paged_window_decode_attention`, at most
`window_pages` pages a lane.

Beside the pools the cache carries what the experts did, as `MLAMoE`'s
does and under the same names (`"moe_load"`, `"moe_step"`).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import regions as R
from ray_tpu.models.config import ConfigDtypes
from ray_tpu.models.gqa import Attention
from ray_tpu.models.moe import STEP_COUNTS, DenseOrRoutedFFN
from ray_tpu.models.paged import (ExpertCounts, Layer, PagedDecoder, Params,
                                  Walk)
from ray_tpu.ops import paged_attention as _paged
from ray_tpu.ops import rope as _rope

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"

@dataclasses.dataclass(frozen=True)
class RopeParams:
    """One kind of layer's rotary scheme, under the published keys of
    `rope_parameters[kind]`. `rope_type` "default" is plain, "yarn" scales
    the frequencies (`ops.rope.yarn_frequencies`) and multiplies cos and
    sin by `attention_factor` (0.1 ln(factor) + 1 where none is given)."""
    rope_theta: float = 10000.0
    rope_type: str = "default"
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise ValueError(f"rope_type {self.rope_type!r}: only "
                             f"\"default\" and \"yarn\" are built")

    def cos_sin(self, positions: jax.Array, head_dim: int):
        """(cos, sin) of the rotated leading part of a head."""
        rot = int(head_dim * self.partial_rotary_factor)
        if self.rope_type == "default":
            return _rope.cos_sin(
                positions, _rope.rope_frequencies(rot, self.rope_theta))
        scale = self.attention_factor
        if scale is None:
            scale = 0.1 * math.log(self.factor) + 1.0
        inv = _rope.yarn_frequencies(
            rot, self.rope_theta, self.factor,
            self.original_max_position_embeddings, self.beta_fast,
            self.beta_slow)
        return _rope.cos_sin(positions, inv, scale)


@dataclasses.dataclass(frozen=True)
class GQAWindowMoEConfig(ConfigDtypes):
    """Fields under the published keys' meanings (`config.json` of
    `laguna`); the per-layer lists are tuples, one entry a layer."""
    vocab_size: int = 100352
    d_model: int = 2048                     # hidden_size
    n_kv_heads: int = 8                     # num_key_value_heads
    head_dim: int = 128
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING, FULL)
    n_heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64, 48)
    mlp_layer_types: Tuple[str, ...] = (DENSE, SPARSE, SPARSE, SPARSE,
                                        SPARSE)
    sliding_window: int = 512
    rope_full: RopeParams = RopeParams(
        rope_theta=500000.0, rope_type="yarn", partial_rotary_factor=0.5,
        factor=64.0, original_max_position_embeddings=4096, beta_fast=64.0,
        beta_slow=1.0, attention_factor=1.4158883083359672)
    rope_sliding: RopeParams = RopeParams(rope_theta=10000.0)
    d_ff: int = 8192                        # intermediate_size (dense)
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 256
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5      # moe_routed_scaling_factor
    max_seq_len: int = 8192
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        # a dict of fields (JSON: lists and nested dicts) names this class
        # as readily as a call does
        for name in ("layer_types", "n_heads_per_layer", "mlp_layer_types"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        for name in ("rope_full", "rope_sliding"):
            if isinstance(getattr(self, name), dict):
                object.__setattr__(self, name,
                                   RopeParams(**getattr(self, name)))
        n = len(self.layer_types)
        if len(self.n_heads_per_layer) != n or len(self.mlp_layer_types) != n:
            raise ValueError("layer_types, n_heads_per_layer and "
                             "mlp_layer_types name one entry a layer")
        if (set(self.layer_types) - {FULL, SLIDING}
                or set(self.mlp_layer_types) - {DENSE, SPARSE}):
            raise ValueError(f"layer kinds {set(self.layer_types)} / "
                             f"{set(self.mlp_layer_types)} not built")
        if any(h % self.n_kv_heads for h in self.n_heads_per_layer):
            raise ValueError("every layer's heads must be a multiple of "
                             "the kv heads")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == FULL)

    @property
    def sliding_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types)
                     if k == SLIDING)

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.mlp_layer_types)
                     if k == SPARSE)

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


def tiny_gqa_window_moe(vocab_size: int = 256) -> GQAWindowMoEConfig:
    """CI/debug model: every mechanism at a size the CPU runs in seconds:
    2 full and 3 sliding layers with unlike head counts, a window of 32
    (5 pages of 8), a dense layer and four of 8 experts top-2, YaRN on
    half a head."""
    return GQAWindowMoEConfig(
        vocab_size=vocab_size, d_model=64, n_kv_heads=2, head_dim=16,
        n_heads_per_layer=(4, 6, 6, 6, 4), sliding_window=32,
        rope_full=RopeParams(
            rope_theta=10000.0, rope_type="yarn", partial_rotary_factor=0.5,
            factor=8.0, original_max_position_embeddings=32, beta_fast=8.0,
            beta_slow=1.0),
        rope_sliding=RopeParams(rope_theta=100.0),
        d_ff=128, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, num_experts=8,
        num_experts_per_tok=2, max_seq_len=256, dtype="float32",
        param_dtype="float32")


class GatedAttention(Attention):
    """A layer's attention: `heads` query heads rotated by the layer
    kind's scheme, a sliding layer's under the window and in a ring of its
    own pools; the heads' outputs gated a head."""

    def __init__(self, config: GQAWindowMoEConfig, kind: str, heads: int):
        c = config
        sliding = kind == SLIDING
        self.rope = c.rope_sliding if sliding else c.rope_full
        super().__init__(
            c.d_model, heads, c.n_kv_heads, c.head_dim, c.activation_dtype,
            *((c.sliding_window, ("wk", "wv")) if sliding else ()))

    def shapes(self, std: float, out_std: float) -> Dict[str, tuple]:
        return {**super().shapes(std, out_std),
                "wg": ((self.d_model, self.heads), std)}    # the gate, a head

    def open(self, at: Walk) -> None:
        """(cos, sin) of the program's positions under this kind's scheme:
        one table a scheme, once a program."""
        if self.rope not in at.tables:
            with R.region(R.ATTN_IN):
                at.tables[self.rope] = self.rope.cos_sin(at.positions(),
                                                         self.head_dim)

    @R.region(R.ATTN_IN)
    def _qkv(self, layer: Params, h, at: Walk):
        cos, sin = at.tables[self.rope]
        q, k, v = super()._qkv(layer, h, at)
        return (_rope.rotate_leading(q, cos, sin),
                _rope.rotate_leading(k, cos, sin), v)

    @R.region(R.ATTN_OUT)
    def _out(self, layer: Params, h, out):
        """Heads' outputs `out` (..., heads, hd), gated a head by the
        sigmoid of a projection of the layer's normed input `h`, through
        W_o."""
        out = out.astype(self.dtype)
        gate = jax.nn.sigmoid(
            (h @ layer["wg"].astype(self.dtype)).astype(jnp.float32))
        return super()._out(layer, h, out * gate[..., None].astype(out.dtype))


class GQAWindowMoE(DenseOrRoutedFFN, ExpertCounts, PagedDecoder):
    """Functional model bundle for one GQAWindowMoEConfig: `init`, `apply`
    / `loss` (a plain forward, the tests' and a trainer's; on a TPU the
    windowed flash kernel has no backward, so a trainer differentiates it
    off the chip only), and what a serving engine asks a model for
    (`models.paged.PagedDecoder`)."""

    no_mesh = "experts and the two pools are not sharded over chips yet"
    # a layer holds all its experts: none is away, no slot computes nothing
    step_count_names = STEP_COUNTS[:3]

    def __init__(self, config: GQAWindowMoEConfig, mesh=None):
        super().__init__(config, mesh)
        c = config
        self._ring_walks: Dict[int, list] = {}  # `fixed_step_counts`'s
        # a mixer a (kind, head count): the full layers' before the rings'
        of = {key: GatedAttention(c, *key) for key in sorted(set(zip(
            c.layer_types, c.n_heads_per_layer)))}
        self._lay(list(of.values()), [
            Layer((of[key],), experts=c.num_experts if ffn == SPARSE else 0)
            for key, ffn in zip(zip(c.layer_types, c.n_heads_per_layer),
                                c.mlp_layer_types)])

    # ------------------------------------------------------------ init
    def layer_shapes(self, i: int) -> Dict[str, Tuple[tuple, float]]:
        """A norm's scale is stored as w, the layer multiplying by 1 + w."""
        c = self.config
        e = c.d_model
        std = 0.02
        out_std = std / math.sqrt(2 * c.n_layers)
        mixer, = self.layers[i].mixers
        shapes = {"attn_norm": ((e,), 0.0), **mixer.shapes(std, out_std),
                  "mlp_norm": ((e,), 0.0)}
        if c.mlp_layer_types[i] == DENSE:
            shapes.update(gate=((e, c.d_ff), std), up=((e, c.d_ff), std),
                          down=((c.d_ff, e), out_std))
            return shapes
        E, f = c.num_experts, c.moe_intermediate_size
        fs = c.shared_expert_intermediate_size
        shapes.update(
            router=((e, E), std),
            moe_gate=((E, e, f), std), moe_up=((E, e, f), std),
            moe_down=((E, f, e), out_std),
            shared_gate=((e, fs), std), shared_up=((e, fs), std),
            shared_down=((fs, e), out_std))
        return shapes

    # --------------------------------------------------------- pieces
    def _routing(self, layer: Params):
        c = self.config                 # no correction bias
        return jnp.zeros((c.num_experts,), jnp.float32), dict(
            top_k=c.num_experts_per_tok, norm_topk_prob=True,
            scale=c.routed_scaling_factor)

    # ------------------------------------------------ what an engine asks
    def window_pages(self, page_size: int) -> int:
        """Pages of a sequence that its sliding layers keep: the ring a
        sequence's first table entries name (0: no such layer)."""
        return self.fixed_pages(page_size)

    def fixed_step_counts(self, length: int, page_size: int,
                          kernel: bool = True) -> Dict[str, int]:
        """What a lane's ring costs a decode step, by the names the
        engine's span carries (`window_positions`)."""
        live, read = self.window_positions(length, page_size, kernel)
        blocks, attended = self._ring_walk(page_size)[
            read // page_size] if kernel else (0, read)
        return {"window_positions_live": live,
                "window_positions_read": read,
                "window_walk_blocks": blocks,
                "window_positions_attended": attended}

    def _ring_walk(self, page_size: int) -> list:
        """`walk_counts` of the kernel's walk over a ring by the pages it
        reaches (the engine asks a lane a step: worked out once)."""
        if page_size not in self._ring_walks:
            ring = self.window_pages(page_size)
            block = self.walk_block_pages(page_size, ring, fixed=True)
            self._ring_walks[page_size] = [
                _paged.walk_counts(n, block, page_size)
                for n in range(ring + 1)]
        return self._ring_walks[page_size]

    def window_positions(self, length: int, page_size: int,
                         kernel: bool = True) -> Tuple[int, int]:
        """(positions a sliding layer holds live, positions its decode
        attention reads) for a lane `length` long: under the kernel the
        pages from the first the window reaches, whole; under the einsum
        the whole ring."""
        live, read = _paged.ring_walk(length, self.config.sliding_window,
                                      page_size)
        if not kernel:
            read = self.window_pages(page_size) * page_size
        return live, read
