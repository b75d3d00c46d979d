"""A decoder whose mixers are of two kinds that keep unlike things of a
sequence, both narrow: delta-rule layers whose decay is a vector over the
key width (KDA, linear attention: a recurrent state of fixed size), and
latent-attention layers (MLA), which hold one latent row a position; and
whose feed-forwards are dense or routed experts chosen under a group
limit, a share of them held here. Named layer by layer by the config's two
lists: the `bailing_hybrid` family's language model (Ling-3.0-flash: five
KDA layers to one latent), on the ops the other classes run on and behind
the same serving engine.

Blocks are pre-norm: `x <- x + Mixer(N(x))`, then `x <- x + FFN(N(x))`; a
final norm before the untied head.

A **linear** layer (`ops.kda`), u its normed input, H heads of key width
dk and value width dv:

    [q~ | k~ | v~] = u W_qkv;  f = u W_f;  b = u W_b;  z = u W_g
    q, k, v = SiLU(causal depthwise conv of width 4 over [q~ | k~ | v~])
    q <- q / |q| / sqrt(dk);  k <- k / |k|                    (a head)
    g = lower_bound sigmoid(exp(A_log) (f + dt_bias))  (a head and channel)
    beta = sigmoid(b)                                         (a head)
    S'_t = Diag(exp(g_t)) S_{t-1}
    S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
    o_t = S_t^T q_t;   y = (RMSNorm_dv(o) * sigmoid(z)) W_o

`g` lies in (`kda_lower_bound`, 0) whatever `f` is, which is what the chunk
kernel's factors need (`ops.kda.lower_bound_fits`). `A_log` and `dt_bias`
are held as offsets from the config's `a_log_init` and `dt_bias_init`, as
a norm's scale is held as an offset from 1.

A **latent** layer is the mixer `models.latent.LatentAttention` with the query in
one matrix (no `q_lora_rank`) and a sigmoid gate a head on the attention's
output (`head_gate`). A **sparse** feed-forward is
`models.moe.DenseOrRoutedFFN`'s: a sigmoid a slot, the choice among the
`topk_group` best of `n_group` groups of slots, `experts_held = (first,
count)` of the `n_routed_experts` computed here (`dropless_moe_ffn(held=)`:
the layer routes over all, computes its own experts' rows and leaves out
what the others would add), a shared expert on every token.

**Two kinds of cache behind one page table** (`models/paged.py` has the
addresses), both small and each its mixer's: a latent layer keeps a
position one row, pool `"kv"`; a linear layer keeps a sequence the same
bytes at any length (`models.hybrid_delta.GatedDelta`'s two pools), at the
slot its first table entry names, which the latent pool backs like any
page. Beside them `paged.ExpertCounts`' two entries.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import regions as R
from ray_tpu.models.hybrid_delta import GatedDelta
from ray_tpu.models.latent import LatentAttention, LatentDims
from ray_tpu.models.moe import DenseOrRoutedFFN
from ray_tpu.models.paged import ExpertCounts, Layer, PagedDecoder, Params
from ray_tpu.ops import kda as _kda
from ray_tpu.ops.gated_delta import CHUNK, l2_normalize
from ray_tpu.ops.norms import rms_norm_reference

LINEAR, LATENT = "linear_attention", "latent_attention"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(frozen=True)
class HybridKDAMoEConfig(LatentDims):
    """Fields under the published keys' meanings (`config.json` of
    `bailing_hybrid`); `layer_types` and `mlp_layer_types` tuples, one
    entry a layer; `n_routed_experts` the experts of the whole layer and
    `experts_held` this chip's."""
    vocab_size: int = 157184
    d_model: int = 2560                     # hidden_size
    layer_types: Tuple[str, ...] = (LINEAR,) * 5 + (LATENT,)
    mlp_layer_types: Tuple[str, ...] = (SPARSE,) * 6
    n_heads: int = 32                       # num_attention_heads, both kinds
    linear_key_dim: int = 128               # head_dim
    linear_value_dim: int = 128
    conv_width: int = 4                     # short_conv_kernel_size
    kda_lower_bound: float = -5.0
    chunk: int = CHUNK                      # positions a prefill chunk
    a_log_init: float = 0.0
    dt_bias_init: float = 0.0
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    head_gate: bool = True      # gated_attention_proj_granularity_type
    d_ff: int = 6144                        # intermediate_size (dense)
    moe_intermediate_size: int = 768
    shared_intermediate_size: int = 768     # moe_shared_expert_inter..._size
    n_routed_experts: int = 512             # num_experts
    experts_held: Optional[Tuple[int, int]] = None   # (first, count); all
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    max_seq_len: int = 16384
    rope_theta: float = 6000000.0
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        for name in ("layer_types", "mlp_layer_types"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if set(self.layer_types) - {LINEAR, LATENT} or set(
                self.mlp_layer_types) - {DENSE, SPARSE}:
            raise ValueError(f"layer kinds {set(self.layer_types)} / "
                             f"{set(self.mlp_layer_types)} not built")
        if len(self.mlp_layer_types) != len(self.layer_types):
            raise ValueError("layer_types and mlp_layer_types name unlike "
                             "numbers of layers")
        if not _kda.lower_bound_fits(self.kda_lower_bound):
            raise ValueError(
                f"kda_lower_bound {self.kda_lower_bound}: the chunk "
                f"kernel's factors need it in [-"
                f"{_kda.MAX_EXPONENT / (_kda.SOLVE_BLOCK // 2):g}, 0)")
        first, count = self.held
        if count < 1 or not 0 <= first <= self.n_routed_experts - count:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.n_routed_experts} experts")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def of_kind(self, kind: str) -> Tuple[int, ...]:
        """The layers whose mixer or feed-forward is of `kind`."""
        return tuple(i for i, kinds in enumerate(zip(
            self.layer_types, self.mlp_layer_types)) if kind in kinds)

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the experts this chip holds."""
        return tuple(self.experts_held or (0, self.n_routed_experts))

    @property
    def key_dim(self) -> int:               # a linear layer's q, k, f width
        return self.n_heads * self.linear_key_dim

    @property
    def value_dim(self) -> int:             # a linear layer's v, z width
        return self.n_heads * self.linear_value_dim

    @property
    def conv_channels(self) -> int:
        return 2 * self.key_dim + self.value_dim


def tiny_hybrid_kda_moe(vocab_size: int = 256,
                        experts_held=(4, 4)) -> HybridKDAMoEConfig:
    """CI/debug model: every mechanism at a size the CPU runs in seconds:
    a dense layer and a period of three (two linear, one latent) over
    experts, 4 heads of 8 / 16, chunks of 8, a latent row of 128 (so the
    latent kernel tiles under the interpreter), 16 experts in 4 groups of
    which 2 are kept, a share of them that does not start at 0."""
    return HybridKDAMoEConfig(
        vocab_size=vocab_size, d_model=64,
        layer_types=(LINEAR, LINEAR, LINEAR, LATENT),
        mlp_layer_types=(DENSE, SPARSE, SPARSE, SPARSE), n_heads=4,
        linear_key_dim=8, linear_value_dim=16, chunk=8, kv_lora_rank=96,
        qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=32, d_ff=128,
        moe_intermediate_size=32, shared_intermediate_size=32,
        n_routed_experts=16, experts_held=experts_held,
        num_experts_per_tok=4, n_group=4, topk_group=2, max_seq_len=256,
        dtype="float32", param_dtype="float32")


class KDA(GatedDelta):
    """The linear layers' mixer: `GatedDelta` with the decay a vector over
    the key width (`ops.kda`), its own gates and a sigmoid on the way
    out."""

    def shapes(self, std: float, out_std: float) -> Dict[str, tuple]:
        c = self.config
        e, H = c.d_model, self.heads
        return {"w_qkv": ((e, c.conv_channels), std),
                "w_f": ((e, c.key_dim), std), "w_b": ((e, H), std),
                "w_g": ((e, c.value_dim), std),
                "conv": ((c.conv_width, c.conv_channels), std),
                "a_log": ((H,), 0.0),
                "dt_bias": ((H, c.linear_key_dim), 0.0),
                "o_norm": ((c.linear_value_dim,), 0.0),
                "wo": ((c.value_dim, e), out_std)}

    def decode_kernel(self, page_size: int, dtype) -> str:
        c = self.config
        return (_kda.KERNEL_STEP if _kda.uses_step_kernel(
            self.heads, c.linear_key_dim, c.linear_value_dim)
            else "kda_gather")

    def _rule(self):
        return _kda.kda_chunked, _kda.kda_prefill, _kda.kda_step

    @R.region(R.MIXER_IN)
    def _inputs(self, layer: Params, u, mixed):
        """What the recurrence takes of positions u (n, e) whose convolved
        channels are `mixed` (n, channels): q, k (n, H, dk) and v (n, H,
        dv) in the activations' dtype, g (n, H, dk) and beta (n, H)
        float32."""
        c = self.config
        ad = c.activation_dtype
        H, dk = self.heads, c.linear_key_dim
        n = u.shape[0]
        q, k, v = jnp.split(mixed, [c.key_dim, 2 * c.key_dim], axis=-1)
        q = l2_normalize(q.reshape(n, H, dk)) * dk ** -0.5
        k = l2_normalize(k.reshape(n, H, dk))
        f32 = jnp.float32           # the offsets are added in float32
        g, beta = _kda.gates(
            (u @ layer["w_f"].astype(ad)).reshape(n, H, dk),
            u @ layer["w_b"].astype(ad),
            c.a_log_init + layer["a_log"].astype(f32),
            c.dt_bias_init + layer["dt_bias"].astype(f32),
            c.kda_lower_bound)
        return (q.astype(ad), k.astype(ad),
                v.reshape(n, H, c.linear_value_dim), g, beta)

    @R.region(R.MIXER_OUT)
    def _out(self, layer: Params, u, o):
        """Heads' outputs o (n, H, dv): normed a head, gated by the
        sigmoid of a projection of the layer's input u (n, e), through
        W_o."""
        c = self.config
        ad = c.activation_dtype
        z = (u @ layer["w_g"].astype(ad)).reshape(o.shape)
        o = rms_norm_reference(o.astype(jnp.float32), layer["o_norm"],
                               c.norm_eps)
        y = (o * jax.nn.sigmoid(z.astype(jnp.float32))).astype(ad)
        return y.reshape(u.shape[0], -1) @ layer["wo"].astype(ad)


class HybridKDAMoE(DenseOrRoutedFFN, ExpertCounts, PagedDecoder):
    """Functional model bundle for one HybridKDAMoEConfig: `init`, `apply`
    / `loss` (the plain chunked form, differentiated by JAX), and what a
    serving engine asks a model for (`models.paged.PagedDecoder`)."""

    no_mesh = ("neither the state pools, the latent cache nor the experts' "
               "exchange over chips have been built")

    def __init__(self, config: HybridKDAMoEConfig, mesh=None):
        super().__init__(config, mesh)
        c = config
        self.attention = LatentAttention(c)
        self.linear = KDA(c, c.n_heads)
        mixers = {LATENT: self.attention, LINEAR: self.linear}
        self._lay([self.attention, self.linear], [
            Layer((mixers[kind],), experts=c.held[1] if ffn == SPARSE else 0)
            for kind, ffn in zip(c.layer_types, c.mlp_layer_types)])

    # ------------------------------------------------------------ init
    def layer_shapes(self, i: int) -> Dict[str, Tuple[tuple, float]]:
        """Zeros are a norm's scale w, the layer multiplying by 1 + w;
        `a_log`, `dt_bias`, offsets from the config's initial values; the
        router's bias."""
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
        E, f, fs = c.held[1], c.moe_intermediate_size, \
            c.shared_intermediate_size
        shapes.update(
            router=((e, c.n_routed_experts), std),
            router_bias=((c.n_routed_experts,), 0.0),
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
            scale=c.routed_scaling_factor, held=c.held, n_group=c.n_group,
            topk_group=c.topk_group)
