"""A decoder whose layers are one mixer each, of three kinds named layer by
layer by the config's pattern: a state-space mixer (a selective scan: a
recurrent state of fixed size a sequence), a mixture of experts that live in
a latent narrower than the stream, or grouped-query attention (the
`nemotron_h` family's hybrid of the three), on the ops the other classes run
on and behind the same serving engine.

Every layer is a pre-norm block, `x' = x + Mixer(N(x))`, `N` an RMSNorm of
its own; there is no second sub-layer. A final norm before the untied head.

`M`, a **state-space** mixer (`models.ssm.SSM` has its mathematics and its
two pools; `ops.ssd` the scan): H heads of width P in G groups, a state of
N numbers a channel, no scale on `W_in`'s columns, the gate before the
norm.

`*`, **attention** (`models.gqa.Attention` as it is): grouped-query softmax
attention, no bias and no rotary embedding (position comes from the
state-space layers).

`E`, **experts in a latent** (`models.moe.dropless_moe_ffn`): the router
reads the stream (a sigmoid a slot in float32, the top k of `score + bias`
chosen, weights renormalised and scaled), the experts a projection of it:

    l = u W_fc1;   E_i(l) = relu(l W_up_i)^2 W_down_i       (two matrices)
    MoE(u) = (sum over chosen i of w_i E_i(l)) W_fc2 + relu(u S_up)^2 S_down

the chosen experts' results summed in the latent, in float32, and `W_fc2`
applied once a token; the shared expert reads the stream. **The layer is
told which experts it holds** (`experts_held = (first, count)` of
`n_routed_experts`), as `ShortcutMLAMoE`'s is: it routes over all of them
and computes its own experts' part, one chip's share of a layer divided over
chips, without the exchange.

**Two kinds of cache behind one page table**, each its mixer's: the
attention layers' pages, the state-space layers' slots. Beside them
`paged.ExpertCounts`' two entries.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import regions as R
from ray_tpu.models import ssm
from ray_tpu.models.config import ConfigDtypes
from ray_tpu.models.gqa import Attention
from ray_tpu.models.moe import dropless_moe_ffn
from ray_tpu.models.paged import ExpertCounts, Layer, PagedDecoder, Params
from ray_tpu.ops import ssd as _ssd

# a layer's kind, by the letters of the family's `hybrid_override_pattern`
SSM, EXPERTS, ATTENTION = "M", "E", "*"


@dataclasses.dataclass(frozen=True)
class HybridSSMMoEConfig(ssm.SSMDims, ConfigDtypes):
    """Fields under the published keys' meanings (`config.json` of
    `nemotron_h`); `layer_types` the pattern, one letter a layer;
    `n_routed_experts` the experts of the whole layer and `experts_held`
    this chip's."""
    vocab_size: int = 131072
    d_model: int = 4096                     # hidden_size
    layer_types: Tuple[str, ...] = tuple("MEMEMEMEM*E")
    n_heads: int = 32                       # num_attention_heads
    n_kv_heads: int = 2                     # num_key_value_heads
    head_dim: int = 128
    ssm_heads: int = 128                    # mamba_num_heads
    ssm_head_dim: int = 64                  # mamba_head_dim
    ssm_groups: int = 8                     # n_groups
    ssm_state: int = 128                    # ssm_state_size
    conv_width: int = 4                     # conv_kernel
    chunk: int = _ssd.CHUNK                 # chunk_size
    a_log_init: float = 0.0
    dt_bias_init: float = 0.0
    d_init: float = 1.0
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    shared_intermediate_size: int = 5376    # moe_shared_expert_inter..._size
    n_routed_experts: int = 512
    experts_held: Optional[Tuple[int, int]] = None   # (first, count); all
    num_experts_per_tok: int = 22
    routed_scaling_factor: float = 5.0
    max_seq_len: int = 8192
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if set(self.layer_types) - {SSM, EXPERTS, ATTENTION}:
            raise ValueError(f"layer kinds {set(self.layer_types)} not "
                             f"built")
        if self.n_heads % self.n_kv_heads or (
                self.ssm_heads % self.ssm_groups):
            raise ValueError("kv heads must divide the heads, the groups "
                             "the state-space heads")
        first, count = self.held
        if count < 1 or not 0 <= first <= self.n_routed_experts - count:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.n_routed_experts} experts")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def of_kind(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == kind)

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the experts this chip holds."""
        return tuple(self.experts_held or (0, self.n_routed_experts))

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


def tiny_hybrid_ssm_moe(vocab_size: int = 256,
                        experts_held=(4, 4)) -> HybridSSMMoEConfig:
    """CI/debug model: every mechanism at a size the CPU runs in seconds
    (one period of all three kinds, 4 state-space heads in 2 groups, 4
    query heads over 2 kv heads, a latent narrower than the stream, a share
    of the experts that does not start at 0, chunks of 8)."""
    return HybridSSMMoEConfig(
        vocab_size=vocab_size, d_model=64, layer_types=tuple("MEM*E"),
        n_heads=4, n_kv_heads=2, head_dim=16, ssm_heads=4, ssm_head_dim=8,
        ssm_groups=2, ssm_state=16, chunk=8, moe_latent_size=32,
        moe_intermediate_size=48, shared_intermediate_size=96,
        n_routed_experts=16, experts_held=experts_held,
        num_experts_per_tok=4, max_seq_len=256, dtype="float32",
        param_dtype="float32")


class HybridSSMMoE(ExpertCounts, PagedDecoder):
    """Functional model bundle for one HybridSSMMoEConfig: `init`, `apply`
    / `loss` (the plain chunked scan, differentiated by JAX), and what a
    serving engine asks a model for (`models.paged.PagedDecoder`)."""

    no_mesh = ("neither the state pools nor the experts' exchange over "
               "chips have been built")

    def __init__(self, config: HybridSSMMoEConfig, mesh=None):
        super().__init__(config, mesh)
        c = config
        self.attention = Attention(c.d_model, c.n_heads, c.n_kv_heads,
                                   c.head_dim, c.activation_dtype)
        self.ssm = ssm.SSM(c)
        # every layer is one mixer behind its norm and no feed-forward, or
        # (an expert layer) the feed-forward behind that norm and no mixer
        rows = {ATTENTION: Layer((self.attention,), "norm", None),
                SSM: Layer((self.ssm,), "norm", None),
                EXPERTS: Layer((), None, "norm", c.held[1])}
        self._lay([self.attention, self.ssm],
                  [rows[kind] for kind in c.layer_types])

    # ------------------------------------------------------------ init
    def layer_shapes(self, i: int) -> Dict[str, Tuple[tuple, float]]:
        """Zeros are a norm's scale w, the layer multiplying by 1 + w;
        `a_log`, `dt_bias`, `d`, offsets from the config's initial values;
        the convolution's and the router's bias."""
        c = self.config
        e = c.d_model
        std = 0.02
        out_std = std / math.sqrt(c.n_layers)
        kind = c.layer_types[i]
        if kind == ATTENTION:
            return {"norm": ((e,), 0.0),
                    **self.attention.shapes(std, out_std)}
        if kind == EXPERTS:
            E, lat, f = c.held[1], c.moe_latent_size, c.moe_intermediate_size
            return {"norm": ((e,), 0.0),
                    "router": ((e, c.n_routed_experts), std),
                    "router_bias": ((c.n_routed_experts,), 0.0),
                    "fc1": ((e, lat), std), "fc2": ((lat, e), out_std),
                    "moe_up": ((E, lat, f), std),
                    "moe_down": ((E, f, lat), std),
                    "shared_up": ((e, c.shared_intermediate_size), std),
                    "shared_down": ((c.shared_intermediate_size, e),
                                    out_std)}
        return {"norm": ((e,), 0.0), **self.ssm.shapes(std, out_std)}

    # --------------------------------------------------------- pieces
    def _ffn(self, layer: Params, u, valid=None):
        """An expert layer's feed-forward on the normed stream u (n, e):
        this chip's experts' part through the latent, and the shared
        expert. Returns (its output, the routed part's counts)."""
        c = self.config
        ad = c.activation_dtype
        with R.region(R.MOE_EXPERTS):       # into the experts' latent
            narrow = u @ layer["fc1"].astype(ad)
        latent, counts = dropless_moe_ffn(
            u, layer["router"], layer["router_bias"], None,
            layer["moe_up"], layer["moe_down"], top_k=c.num_experts_per_tok,
            scale=c.routed_scaling_factor, valid=valid, held=c.held,
            expert_form="relu2", expert_input=narrow)
        with R.region(R.FFN):               # the shared expert
            shared = jnp.square(jax.nn.relu(
                u @ layer["shared_up"].astype(ad)))
        with R.region(R.MOE_EXPERTS):       # and back out of it
            routed = latent @ layer["fc2"].astype(ad)
        with R.region(R.FFN):
            return routed + shared @ layer["shared_down"].astype(ad), counts
