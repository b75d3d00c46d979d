"""A latent-attention decoder whose layers are of two kinds, named layer by
layer by its config's `layer_types`, each kind with a latent geometry of
its own (`dots3_note`: dots3-note-prev), one chip's share of its experts
held, on the ops `SparseMLAMoE` runs on and behind the same serving engine.

With `x` the normed input of a layer's attention, both kinds, each with its
own `(heads, q_lora, kv_lora, nope, rope, v, theta)`:

    c_q  = a_q RMSNorm(x W_qa);  q = c_q W_qb -> heads of [q_nope | RoPE(q_rope)]
    [c_kv | k_rope] = x W_kva;   c_kv = a_kv RMSNorm(c_kv);  k_rope = RoPE(k_rope)
    [k_nope | v]_h = c_kv W_kvb; scores = q . [k_nope | k_rope] / sqrt(nope + rope)
    o = concat_h( sigmoid((x W_g)_h) (P v)_h ) W_o
    a_q = sqrt(d_model / q_lora),  a_kv = sqrt(d_model / kv_lora)

(`models/latent.py` has the scales and the gate: `a_kv` multiplies the row
the cache holds, once, when it is written.)

- A **full** layer is `SparseMLAMoE`'s: an indexer of `index_n_heads` heads
  reads the scaled query latent `c_q` and the normed stream, keeps an index
  key a position in the pool `"idx"` under the latent pool `"kv"`'s page
  ids, and the softmax runs over the `min(index_topk, t + 1)` positions of
  largest index score (`models/sparse_mla_moe.py` has the equations).
- A **sliding** layer has no indexer: its softmax runs over the query's
  last `sliding_window` positions, its own among them, and its rows, wider
  than a full layer's, lie in a ring of the allocator's fixed class under
  `"kv_w"` (`latent.WindowLatentAttention`).

So a sequence holds a ring, a latent pool and an index pool behind its one
page table, and the class is a table of the two mixers (`models/paged.py`
walks it). The feed-forwards are `SparseMLAMoE`'s: `first_k_dense_replace`
leading SwiGLU layers, then sigmoid scores, the top-k of score + bias,
weights from the scores alone, normalised and scaled, over the experts
held here (`experts_held`), plus the shared expert.

Beside the pools the cache carries `SparseMLAMoE`'s counts (`"moe_load"`,
`"moe_step"`, `"dsa_step"`, summed over the full layers) and `"ring_step"`
(`latent.RING_COUNTS`, summed over the sliding layers).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax.numpy as jnp

from ray_tpu.models.latent import WindowLatentAttention
from ray_tpu.models.paged import Layer, PagedDecoder, Params
from ray_tpu.models.sparse_mla_moe import (SparseLatentAttention,
                                           SparseMLAMoE, SparseMLAMoEConfig)
from ray_tpu.ops import paged_attention as _paged

FULL, SLIDING = "full_attention", "sliding_attention"


# the fields a kind of layer has a latent geometry of its own in: the
# sliding layers' under the same names behind `swa_`
GEOMETRY = ("n_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rope_theta")


@dataclasses.dataclass(frozen=True)
class SparseWindowMLAMoEConfig(SparseMLAMoEConfig):
    """`SparseMLAMoEConfig` (the full layers' geometry under its fields)
    and the sliding layers' under the published `swa_` keys' meanings
    (`config.json` of `dots3_note`). `layer_types` names a kind a layer;
    empty, it is the published pattern over `n_layers`: full, full, then
    sliding x 3 and full in turn."""
    vocab_size: int = 152064
    d_model: int = 5120
    n_layers: int = 46
    n_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 80000000.0
    swa_n_heads: int = 64                   # swa_num_attention_heads
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    sliding_window: int = 513               # sliding_window_size
    layer_types: Tuple[str, ...] = ()
    head_gate: bool = True                  # attention_gate_type headwise
    lora_rescale: bool = True               # apply_mla_qkv_lora_rescale
    index_n_heads: int = 64
    # the shortest prompt program: a shorter prompt is padded to it (0: the
    # engine's bucket as it comes). A deployment's, not the model's
    min_prefill: int = 0
    d_ff: int = 13824
    moe_intermediate_size: int = 1536
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 1.0
    max_seq_len: int = 16384

    def __post_init__(self):
        kinds = tuple(self.layer_types) or tuple(
            FULL if i == 0 or i % 4 == 1 else SLIDING
            for i in range(self.n_layers))
        object.__setattr__(self, "layer_types", kinds)
        super().__post_init__()
        if len(kinds) != self.n_layers or set(kinds) - {FULL, SLIDING}:
            raise ValueError(f"layer_types names {len(kinds)} layers of "
                             f"kinds {sorted(set(kinds))}: one of {FULL!r} "
                             f"and {SLIDING!r} for each of {self.n_layers}")
        if self.sliding_window < 1:
            raise ValueError(f"sliding_window {self.sliding_window}")

    @property
    def q_lora_scale(self) -> float:
        return math.sqrt(self.d_model / self.q_lora_rank) if (
            self.lora_rescale) else 1.0

    @property
    def kv_lora_scale(self) -> float:
        return math.sqrt(self.d_model / self.kv_lora_rank) if (
            self.lora_rescale) else 1.0

    def geometry(self, kind: str) -> "SparseWindowMLAMoEConfig":
        """The config as a mixer of the layers of `kind` reads it: a
        sliding layer's with the `swa_` fields under the plain names (the
        two scales follow the ranks)."""
        if kind == FULL:
            return self
        return dataclasses.replace(self, **{
            name: getattr(self, "swa_" + name) for name in GEOMETRY})


def tiny_sparse_window_mla_moe(vocab_size: int = 256, experts_held=(4, 4),
                               index_topk: int = 32, sliding_window: int = 21
                               ) -> SparseWindowMLAMoEConfig:
    """CI/debug model: every mechanism at a size the CPU runs in seconds
    (two full and three sliding layers of unlike geometries, both scales
    other than 1, a gate a head, contexts that pass `index_topk` and a
    window that is no multiple of a page, a share of the experts that does
    not start at 0)."""
    return SparseWindowMLAMoEConfig(
        vocab_size=vocab_size, d_model=64, n_layers=5, n_heads=4,
        q_lora_rank=48, kv_lora_rank=96, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=32, rope_theta=10000.0,
        swa_n_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=128,
        swa_qk_nope_head_dim=32, swa_qk_rope_head_dim=16, swa_v_head_dim=16,
        swa_rope_theta=100.0, sliding_window=sliding_window, d_ff=128,
        moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1,
        num_experts_per_tok=2, first_k_dense_replace=1,
        routed_scaling_factor=1.0, index_n_heads=16, index_head_dim=32,
        index_topk=index_topk, experts_held=experts_held, max_seq_len=128,
        norm_eps=1e-5, dtype="float32", param_dtype="float32")


class SparseWindowMLAMoE(SparseMLAMoE):
    """Functional model bundle for one SparseWindowMLAMoEConfig: `init`,
    `apply` / `loss`, and what a serving engine asks a model for
    (`models.paged.PagedDecoder`)."""

    no_mesh = "experts, the latent cache, its ring and the index keys are " \
              "not sharded over chips yet"

    def __init__(self, config: SparseWindowMLAMoEConfig, mesh=None):
        PagedDecoder.__init__(self, config, mesh)
        c = config
        # the full layers' mixer (`SparseMLAMoE`'s `attention`: what
        # `benchmarks/tools/dsa_sets.py` reads) and the sliding layers'
        self.attention = SparseLatentAttention(c.geometry(FULL))
        self.window_attention = WindowLatentAttention(
            c.geometry(SLIDING), c.sliding_window)
        of = {FULL: self.attention, SLIDING: self.window_attention}
        self._lay(list(of.values()), [
            Layer((of[kind],), experts=self._experts_held() if (
                i >= c.first_k_dense_replace) else 0)
            for i, kind in enumerate(c.layer_types)])

    # ------------------------------------------------------------ init
    def layer_shapes(self, i: int) -> Dict[str, Tuple[tuple, float]]:
        """`SparseMLAMoE`'s leaves; a sliding layer's attention in the
        place of the full one's and its indexer's."""
        shapes = super().layer_shapes(i)
        mixer, = self.layers[i].mixers
        if mixer is not self.attention:
            std = 0.02
            for name in (*self.attention.shapes(std, std),
                         *self.attention.index_shapes(std)):
                del shapes[name]
            shapes.update(mixer.shapes(
                std, std / math.sqrt(2 * self.config.n_layers)))
        return shapes

    def prefill(self, params: Params, tokens, true_len, page_table, cache,
                page_size: int):
        """`PagedDecoder.prefill`, a prompt shorter than the config's
        `min_prefill` padded to it (positions past `true_len` write no
        page and are given to no expert, as a bucket's own padding)."""
        short = self.config.min_prefill - tokens.shape[0]
        if short > 0:
            tokens = jnp.pad(tokens, (0, short))
        return super().prefill(params, tokens, true_len, page_table, cache,
                               page_size)

    # ------------------------------------------------ what an engine asks
    def fixed_step_counts(self, length: int, page_size: int,
                          kernel: bool = True) -> Dict[str, int]:
        """What a lane's ring costs a sliding layer's decode step, by the
        names the engine's span carries: the positions the window holds,
        and those the walk copies in (whole pages from the first the window
        reaches; under the gather the whole ring)."""
        live, read = _paged.ring_walk(length, self.config.sliding_window,
                                      page_size)
        if not kernel:
            read = self.fixed_pages(page_size) * page_size
        return {"window_positions_live": live,
                "window_positions_read": read}
