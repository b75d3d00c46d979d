"""A decoder with multi-head latent attention (MLA) and routed plus shared
experts: the `glm4_moe_lite` / DeepSeek-V2-V3 family of layers, on the
same ops as `Transformer` and behind the same serving engine.

Every layer's attention is MLA. With `x` the normed input:

    c_q  = RMSNorm(x W_qa)                      (q_lora_rank)
    q    = c_q W_qb        -> heads of [q_nope | q_rope]
    [c_kv | k_rope] = x W_kva;  c_kv = RMSNorm(c_kv);  k_rope = RoPE(k_rope)
    [k_nope | v] a head = c_kv W_kvb;           q_rope = RoPE(q_rope)
    scores = q . [k_nope | k_rope] / sqrt(nope + rope), causal softmax
    o = concat_h(P v) W_o

`k_rope` is one for all heads; the attention is the mixer
`models.latent.LatentAttention`, shared with `ShortcutMLAMoE`. The first
`first_k_dense_replace` layers have a SwiGLU feed-forward, the rest
`models.moe.dropless_moe_ffn` (scores in float32, sigmoid as published,
top-k of score + bias, weights from the scores alone, normalised and
scaled) plus `n_shared_experts` shared experts applied to
every token. The layers are unlike, so they are held per layer (a list),
not stacked, and every program unrolls them.

**The cache is latent**: a position costs a layer one row `[c_kv | k_rope]`
(after the norm and the rotation), `kv_lora_rank + qk_rope_head_dim`
numbers against `heads * (qk + v)` uncompressed, padded with zeros to whole
128-lanes (`row_width`; the padding is this layout's cost). The pool is
`(layers, pages, page_size, row_width)` under the key `"kv"`, paged as
`models.decode`'s is. `prefill` runs the expanded form above through
`flash_attention` (keys `nope + rope` wide, values `v_head_dim`, the same
in the published sizes; the cache never holds them) and writes whole pages
in place. `decode_step` runs the absorbed form: `q_lat = q_nope W_UK^T`,
scores `q_lat . c_kv + q_rope . k_rope`, `o_lat = P c_kv`, `o = o_lat W_UV`
— one row read once, key and value both — through
`ops.paged_attention.mla_paged_decode_attention`.

Beside the pool the cache carries what the experts did
(`paged.ExpertCounts`): `"moe_load"` and `"moe_step"`, the last decode
step's `moe_pairs`, `moe_experts_touched` and `moe_load_max` (the busiest
expert's pairs).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

from ray_tpu.models.latent import LatentAttention, LatentDims
from ray_tpu.models.moe import STEP_COUNTS, DenseOrRoutedFFN
from ray_tpu.models.paged import ExpertCounts, Layer, PagedDecoder, Params


@dataclasses.dataclass(frozen=True)
class MLAMoEConfig(LatentDims):
    """Fields under the published keys' meanings (`config.json` of
    `glm4_moe_lite`); `head_dim` is not `d_model / n_heads` here."""
    vocab_size: int = 154880
    d_model: int = 2048                     # hidden_size
    n_layers: int = 47                      # num_hidden_layers
    n_heads: int = 20                       # num_attention_heads
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    d_ff: int = 10240                       # intermediate_size (dense)
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    max_seq_len: int = 4096
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        # `route_topk` scores by softmax too (`ShortcutMLAMoE` does); this
        # class's plain reference writes down the sigmoid alone
        if self.scoring_func != "sigmoid":
            raise ValueError(
                f"scoring {self.scoring_func!r}: this class has been held "
                f"to its reference under sigmoid scoring only")

    @property
    def n_moe_layers(self) -> int:
        return max(0, self.n_layers - self.first_k_dense_replace)


def tiny_mla_moe(vocab_size: int = 256) -> MLAMoEConfig:
    """CI/debug model: every mechanism at a size the CPU runs in seconds
    (row width 128, so the kernels tile under the interpreter)."""
    return MLAMoEConfig(
        vocab_size=vocab_size, d_model=64, n_layers=3, n_heads=4,
        q_lora_rank=48, kv_lora_rank=96, qk_nope_head_dim=16,
        qk_rope_head_dim=16, v_head_dim=32, d_ff=128,
        moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1,
        num_experts_per_tok=2, first_k_dense_replace=1, max_seq_len=128,
        dtype="float32", param_dtype="float32")


class MLAMoE(DenseOrRoutedFFN, ExpertCounts, PagedDecoder):
    """Functional model bundle for one MLAMoEConfig: `init`, `apply` /
    `loss` (training graph; no auxiliary term: the published routing has a
    bias moved between steps, not a loss), and what a serving engine asks a
    model for (`models.paged.PagedDecoder`)."""

    no_mesh = "experts and the latent cache are not sharded over chips yet"
    # a layer holds all its experts: none is away, no slot computes nothing
    step_count_names = STEP_COUNTS[:3]
    # the attention's mixer; a subclass names another
    attention_type = LatentAttention

    def __init__(self, config: MLAMoEConfig, mesh=None):
        super().__init__(config, mesh)
        c = config
        self.attention = self.attention_type(c)
        self._lay([self.attention], [
            Layer((self.attention,), experts=self._experts_held() if (
                i >= c.first_k_dense_replace) else 0)
            for i in range(c.n_layers)])

    def _experts_held(self) -> int:
        return self.config.n_routed_experts

    # ------------------------------------------------------------ init
    def layer_shapes(self, i: int) -> Dict[str, Tuple[tuple, float]]:
        """A norm's scale is stored as w, the layer multiplying by 1 + w."""
        c = self.config
        e = c.d_model
        std = 0.02
        out_std = std / math.sqrt(2 * c.n_layers)
        shapes = {"attn_norm": ((e,), 0.0),
                  **self.attention.shapes(std, out_std),
                  "mlp_norm": ((e,), 0.0)}
        if i < c.first_k_dense_replace:
            shapes.update(gate=((e, c.d_ff), std), up=((e, c.d_ff), std),
                          down=((c.d_ff, e), out_std))
            return shapes
        E, f = c.n_routed_experts, c.moe_intermediate_size
        fs = f * c.n_shared_experts
        shapes.update(
            router=((e, E), std), router_bias=((E,), 0.0),
            moe_gate=((E, e, f), std), moe_up=((E, e, f), std),
            moe_down=((E, f, e), out_std),
            shared_gate=((e, fs), std), shared_up=((e, fs), std),
            shared_down=((fs, e), out_std))
        return shapes

    # --------------------------------------------------------- pieces
    def _routing(self, layer: Params):
        c = self.config
        return layer["router_bias"], dict(
            top_k=c.num_experts_per_tok, norm_topk_prob=c.norm_topk_prob,
            scale=c.routed_scaling_factor)
