"""A decoder whose every layer runs two mixers side by side on one normed
input and sums them: a state-space mixer (a selective scan: a recurrent
state of fixed size a sequence) and grouped-query attention (pages that
grow with it), then a SwiGLU feed-forward; every projection's input or
output is scaled by a published scalar (the `falcon_h1` family's
maximal-update multipliers), on the ops the other classes run on and behind
the same serving engine.

With `N_1`, `N_2`, `N_f` RMSNorms and the twelve multipliers as the config
names them:

    x_0 = embedding_multiplier * E[token]
    h = N_1(x)
    x = x + ssm_out_multiplier * SSM(ssm_in_multiplier * h)
          + attention_out_multiplier * Attn(attention_in_multiplier * h)
    x = x + MLP(N_2(x))
    logits = lm_head_multiplier * (N_f(x) W_head)

`SSM(u)` is `models.ssm.SSM`'s, `W_in`'s product multiplied column by
column by `m`: `ssm_multipliers[0..4]` over the z, x, B, C and dt columns;
the gated norm in the order `mamba_norm_before_gate` says.

`Attn(u)`: `q = u W_q`, `k = key_multiplier * (u W_k)`, `v = u W_v`; q and
k rotated over the whole head (`rope_theta`), the key scaled before it is
rotated and written to its page; causal softmax of `q . k / sqrt(head
dim)`; `W_o`. No bias.

`MLP(h) = (SiLU(mlp_multipliers[0] * (h W_gate)) * (h W_up)) W_down *
mlp_multipliers[1]`.

The multipliers are applied where the published forward applies them, in
the activations' dtype (the head's on the float32 logits); none is folded
into a weight.

**Both kinds of cache in every layer, behind one page table**: pools `"k"`,
`"v"` `(layers, num_pages, page, kv heads x head dim)` (`models/gqa.py`)
and `"state"`, `"tail"` `(layers, slots + 1, ...)` (`models.ssm.SSM`),
all four over all the layers and under the same layer index. A sequence's
first table entry is a page of the allocator's fixed class: it names the
slot of its states and is, like every later entry, a page of its keys and
values. A row of the table holds both mixers, which read the same `h`; the
two do not depend on each other: the order they are issued in (attention,
then the scan) is no order the compiler must keep.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax

from ray_tpu.models import regions as R
from ray_tpu.models.config import ConfigDtypes
from ray_tpu.models.gqa import Attention
from ray_tpu.models.paged import Layer, PagedDecoder, Params, Walk
from ray_tpu.models.ssm import SSM, SSMDims
from ray_tpu.ops import rope as _rope
from ray_tpu.ops import ssd as _ssd


@dataclasses.dataclass(frozen=True)
class ParallelHybridConfig(SSMDims, ConfigDtypes):
    """Fields under the published keys' meanings (`config.json` of
    `falcon_h1`); the twelve multipliers under the published names."""
    vocab_size: int = 261120
    d_model: int = 5120                     # hidden_size
    n_layers: int = 72                      # num_hidden_layers
    n_heads: int = 20                       # num_attention_heads
    n_kv_heads: int = 4                     # num_key_value_heads
    head_dim: int = 128
    rope_theta: float = 1e11
    ssm_heads: int = 32                     # mamba_n_heads
    ssm_head_dim: int = 128                 # mamba_d_head
    ssm_groups: int = 2                     # mamba_n_groups
    ssm_state: int = 256                    # mamba_d_state
    conv_width: int = 4                     # mamba_d_conv
    chunk: int = _ssd.CHUNK                 # mamba_chunk_size
    mamba_norm_before_gate: bool = False
    a_log_init: float = 0.0
    dt_bias_init: float = 0.0
    d_init: float = 1.0
    d_ff: int = 21504                       # intermediate_size
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: Tuple[float, ...] = (     # on z, x, B, C, dt
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    mlp_multipliers: Tuple[float, float] = (   # gate's input, down's output
        0.1767766952966369, 0.011160714285714284)
    max_seq_len: int = 2560
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        for name, n in (("ssm_multipliers", 5), ("mlp_multipliers", 2)):
            values = tuple(float(v) for v in getattr(self, name))
            if len(values) != n:
                raise ValueError(f"{name} {values}: {n} scalars")
            object.__setattr__(self, name, values)
        if self.n_heads % self.n_kv_heads or (
                self.ssm_heads % self.ssm_groups):
            raise ValueError("kv heads must divide the heads, the groups "
                             "the state-space heads")

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


def tiny_parallel_hybrid(vocab_size: int = 256) -> ParallelHybridConfig:
    """CI/debug model: every mechanism at a size the CPU runs in seconds
    (two layers, 10 query heads over 2 kv heads: a group of 5; 4
    state-space heads in 2 groups, chunks of 8; every multiplier unlike 1
    and unlike the others)."""
    return ParallelHybridConfig(
        vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=10,
        n_kv_heads=2, head_dim=16, rope_theta=1e4, ssm_heads=4,
        ssm_head_dim=8, ssm_groups=2, ssm_state=16, chunk=8, d_ff=96,
        embedding_multiplier=3.0, lm_head_multiplier=0.4,
        attention_in_multiplier=1.5, attention_out_multiplier=0.7,
        key_multiplier=0.3, ssm_in_multiplier=0.8, ssm_out_multiplier=1.3,
        ssm_multipliers=(0.9, 1.2, 0.5, 1.6, 0.4),
        mlp_multipliers=(1.4, 0.35), max_seq_len=256, dtype="float32",
        param_dtype="float32")


class ScaledAttention(Attention):
    """The layers' attention: its input, its key and its output each
    times a published scalar, q and k rotated over the whole head."""

    def __init__(self, config: ParallelHybridConfig):
        c = self.config = config
        super().__init__(c.d_model, c.n_heads, c.n_kv_heads, c.head_dim,
                         c.activation_dtype)

    @R.region(R.ATTN_IN)
    def _qkv(self, layer: Params, h, at: Walk):
        """h (..., e) normed -> q (..., heads, hd), k, v (..., kv heads,
        hd): the key scaled, then q and k rotated."""
        c = self.config
        positions = at.positions_along(h)
        q, k, v = super()._qkv(layer, h * c.attention_in_multiplier, at)
        cos, sin = _rope.rope_cos_sin(positions, c.head_dim, c.rope_theta)
        return (_rope.apply_rope_cached(q, cos, sin),
                _rope.apply_rope_cached(k * c.key_multiplier, cos, sin), v)

    @R.region(R.ATTN_OUT)
    def _out(self, layer: Params, h, out):
        return super()._out(layer, h, out
                            ) * self.config.attention_out_multiplier


class ParallelHybrid(PagedDecoder):
    """Functional model bundle for one ParallelHybridConfig: `init`,
    `apply` / `loss` (the plain chunked scan, differentiated by JAX), and
    what a serving engine asks a model for (`models.paged.PagedDecoder`)."""

    no_mesh = ("neither the state pools nor a layer's two mixers are "
               "sharded over chips yet")

    def __init__(self, config: ParallelHybridConfig, mesh=None):
        super().__init__(config, mesh)
        c = config
        self.attention = ScaledAttention(c)
        self.ssm = SSM(c, c.ssm_in_multiplier, c.ssm_multipliers,
                       c.mamba_norm_before_gate)
        self._lay([self.attention, self.ssm],
                  [Layer((self.attention, self.ssm), "norm")] * c.n_layers)

    # ------------------------------------------------------------ init
    def layer_shapes(self, i: int) -> Dict[str, Tuple[tuple, float]]:
        """Every layer alike: attention, the state-space mixer, the
        feed-forward and two norms (zeros are a norm's scale w, the layer
        multiplying by 1 + w, and the mixer's offsets and bias)."""
        c = self.config
        e, f = c.d_model, c.d_ff
        std = 0.02
        out_std = std / math.sqrt(2 * c.n_layers)
        return {"norm": ((e,), 0.0), **self.attention.shapes(std, out_std),
                **self.ssm.shapes(std, out_std),
                "mlp_norm": ((e,), 0.0), "gate": ((e, f), std),
                "up": ((e, f), std), "down": ((f, e), out_std)}

    # --------------------------------------------------------- pieces
    @R.region(R.EMBED)
    def _embed(self, params: Params, tokens):
        c = self.config
        return params["embed"].astype(c.activation_dtype)[
            tokens] * c.embedding_multiplier

    def _add(self, row: Layer, layer: Params, x, outs):
        attn, ssm = outs
        with R.region(R.MIXER_OUT):     # both mixers' residual addition
            return x + ssm * self.config.ssm_out_multiplier + attn

    @R.region(R.FFN)
    def _ffn(self, layer: Params, h, valid=None):
        """The SwiGLU on the second norm, its gate's input and its output
        each times a scalar."""
        c = self.config
        ad = c.activation_dtype
        gate = jax.nn.silu(h @ layer["gate"].astype(ad)
                           * c.mlp_multipliers[0])
        return ((gate * (h @ layer["up"].astype(ad)))
                @ layer["down"].astype(ad)) * c.mlp_multipliers[1], None

    @R.region(R.HEAD)
    def apply(self, params: Params, tokens: jax.Array) -> jax.Array:
        return super().apply(params, tokens) * self.config.lm_head_multiplier

    @R.region(R.HEAD)
    def _logits(self, params: Params, x, true_len=None):
        return super()._logits(params, x, true_len
                               ) * self.config.lm_head_multiplier
