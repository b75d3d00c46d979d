"""A decoder whose layers are of two kinds that keep unlike things of a
sequence: gated delta-rule layers (linear attention), which hold a
recurrent state of fixed size, and full-attention layers, which hold
pages; named layer by layer by the config's list: the `olmo_hybrid`
family (Olmo-Hybrid-7B, three linear layers to one full), on the same ops
as the other three classes and behind the same serving engine.

Blocks are post-norm: `x <- x + RMSNorm(Mixer(x))`, then `x <- x +
RMSNorm(MLP(x))`, the MLP a SwiGLU; a final norm before the untied head.

A **linear** layer (`ops.gated_delta`), x its input, H heads of key width
dk and value width dv:

    [q~ | k~ | v~] = x W_qkv;  z = x W_z;  [a | b] = x W_ab
    q, k, v = SiLU(causal depthwise conv of width 4 over [q~ | k~ | v~])
    q <- q / |q| / sqrt(dk);  k <- k / |k|                    (a head)
    beta = sigmoid(b) (x 2 with `allow_neg_eigval`);
    g = -exp(A_log) softplus(a + dt_bias)          (one number a head)
    S'_t = exp(g_t) S_{t-1};  S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
    o_t = S_t^T q_t;   y = (RMSNorm_dv(o) * SiLU(z)) W_o

`A_log` and `dt_bias` are held as offsets from the config's `a_log_init`
and `dt_bias_init` (0 and 0: the published form), as a norm's scale is
held as an offset from 1.

A **full** layer: plain multi-head attention (`n_kv_heads` = `n_heads`
here, any GQA grouping in general), q and k RMS-normed over their whole
width before the heads are split, no rotary embedding: position comes
from the recurrent layers.

**Two kinds of cache behind one page table** (`models/paged.py` has the
addresses). A full layer keeps every position: pools `"k"`, `"v"` of
`(full layers, num_pages, page, kv x hd)`, `models/gqa.py`'s. A linear
layer keeps a sequence the same bytes at 100 positions and at 3,000: pools
`"state"` `(linear layers, slots + 1, dk, H x dv)` float32 and `"tail"`
(the convolution's last `width - 1` inputs, `(linear layers, slots + 1,
*tail_shape)`: each input folded into rows of whole lanes,
`ops.conv.tail_shape`), a sequence's at the slot its first table
entry names (a `paged.Pool` of kind SLOT: a page of the allocator's fixed class, which
names a state of any shape the model holds and prices, here a delta
rule's), which the full layers' pools back like any page: one table serves
both kinds and nothing is keyed by lane. `prefill` scans a prompt from a
zero state (`gated_delta_prefill`: the chunk kernel, which stops at the
prompt's true length inside its bucket) and writes the slot whole;
`decode_step` updates the slots of active lanes in place
(`conv_tail_step`, then `gated_delta_step`) and leaves every other alone.
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
from ray_tpu.models.moe import swiglu
from ray_tpu.models.paged import (SLOT, Cache, Layer, Mixer, PagedDecoder,
                                  Params, Pool, Walk, write_slot)
from ray_tpu.ops import gated_delta as _gd
from ray_tpu.ops.conv import causal_conv, conv_tail_step, tail_shape
from ray_tpu.ops.norms import rms_norm_reference

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class HybridDeltaConfig(ConfigDtypes):
    """Fields under the published keys' meanings (`config.json` of
    `olmo_hybrid`); `layer_types` a tuple, one entry a layer."""
    vocab_size: int = 100352
    d_model: int = 3840                     # hidden_size
    n_heads: int = 30                       # num_attention_heads
    n_kv_heads: int = 30                    # num_key_value_heads
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL)
    linear_heads: int = 30      # linear_num_key_heads = ..._value_heads
    linear_key_dim: int = 96                # linear_key_head_dim
    linear_value_dim: int = 192             # linear_value_head_dim
    conv_width: int = 4                     # linear_conv_kernel_dim
    allow_neg_eigval: bool = True           # linear_allow_neg_eigval
    chunk: int = _gd.CHUNK                  # positions a prefill chunk
    a_log_init: float = 0.0
    dt_bias_init: float = 0.0
    d_ff: int = 11008                       # intermediate_size
    max_seq_len: int = 3072
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if set(self.layer_types) - {LINEAR, FULL}:
            raise ValueError(f"layer kinds {set(self.layer_types)} not "
                             f"built")
        if self.d_model % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError("heads must divide the width, kv heads the "
                             "heads")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == FULL)

    @property
    def linear_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types)
                     if k == LINEAR)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def key_dim(self) -> int:               # a linear layer's q, k width
        return self.linear_heads * self.linear_key_dim

    @property
    def value_dim(self) -> int:             # a linear layer's v, z width
        return self.linear_heads * self.linear_value_dim

    @property
    def conv_channels(self) -> int:
        return 2 * self.key_dim + self.value_dim


def tiny_hybrid_delta(vocab_size: int = 256) -> HybridDeltaConfig:
    """CI/debug model: every mechanism at a size the CPU runs in seconds:
    two periods of three linear layers and a full one, 4 heads of 8 / 16,
    chunks of 8."""
    return HybridDeltaConfig(
        vocab_size=vocab_size, d_model=64, n_heads=4, n_kv_heads=4,
        layer_types=(LINEAR, LINEAR, LINEAR, FULL) * 2, linear_heads=4,
        linear_key_dim=8, linear_value_dim=16, chunk=8, d_ff=128,
        max_seq_len=256, dtype="float32", param_dtype="float32")


class NormedAttention(Attention):
    """The full layers' attention: q and k RMS-normed over their whole
    width (`norm`: the model's norm kernel) before the heads are split."""

    def __init__(self, *widths, norm):
        super().__init__(*widths)
        self._norm = norm

    def shapes(self, std: float, out_std: float) -> Dict[str, tuple]:
        return {**super().shapes(std, out_std),
                "q_norm": ((self.heads * self.head_dim,), 0.0),
                "k_norm": ((self.kv_dim,), 0.0)}

    @R.region(R.ATTN_IN)
    def _qkv(self, layer: Params, x, at: Walk):
        """x (..., e) -> q (..., heads, hd), k, v (..., kv heads, hd), q
        and k normed over their whole width first, all three before any
        is split (`Attention._qkv` splits each as it is projected: another
        text)."""
        ad = self.dtype
        q = self._norm(x @ layer["wq"].astype(ad), layer["q_norm"])
        k = self._norm(x @ layer["wk"].astype(ad), layer["k_norm"])
        v = x @ layer["wv"].astype(ad)
        lead = x.shape[:-1]
        return (q.reshape(*lead, self.heads, self.head_dim),
                k.reshape(*lead, self.kv_heads, self.head_dim),
                v.reshape(*lead, self.kv_heads, self.head_dim))


class GatedDelta(Mixer):
    """The linear layers' mixer: the gated delta rule of `heads` heads
    behind a causal convolution, over the widths `config` names
    (`linear_key_dim`, `linear_value_dim`, `key_dim`, `value_dim`,
    `conv_channels`, `conv_width`, `chunk`). What it keeps of a sequence:
    `"state"` `(layers, slots + 1, dk, H x dv)` float32 and the
    convolution's `"tail"`. `models.hybrid_kda_moe.KDA` is this mixer with
    the decay a vector over the key width."""

    def __init__(self, config, heads: int):
        c = self.config = config
        self.heads, self.chunk = heads, c.chunk
        self.pools = (
            Pool("state", SLOT, (c.linear_key_dim, c.value_dim),
                 jnp.float32),
            Pool("tail", SLOT, tail_shape(c.conv_width, c.conv_channels)))

    def shapes(self, std: float, out_std: float) -> Dict[str, tuple]:
        c = self.config
        e, H = c.d_model, self.heads
        return {"w_qkv": ((e, c.conv_channels), std),
                "w_z": ((e, c.value_dim), std), "w_ab": ((e, 2 * H), std),
                "conv": ((c.conv_width, c.conv_channels), std),
                "a_log": ((H,), 0.0), "dt_bias": ((H,), 0.0),
                "o_norm": ((c.linear_value_dim,), 0.0),
                "wo": ((c.value_dim, e), out_std)}

    def decode_kernel(self, page_size: int, dtype) -> str:
        c = self.config
        return (_gd.KERNEL_STEP if _gd.uses_step_kernel(
            self.heads, c.linear_key_dim, c.linear_value_dim)
            else "gated_delta_gather")

    def _rule(self):
        """The rule's (plain chunked form, prefill, step), looked up at
        the call."""
        return (_gd.gated_delta_chunked, _gd.gated_delta_prefill,
                _gd.gated_delta_step)

    # --------------------------------------------------------- pieces
    @R.region(R.MIXER_IN)
    def _inputs(self, layer: Params, x, mixed):
        """What the recurrence takes of positions x (n, e) whose
        convolved channels are `mixed` (n, channels): q, k (n, H, dk) and
        v (n, H, dv) in the activations' dtype, g, beta (n, H) float32."""
        c = self.config
        ad = c.activation_dtype
        H, dk = self.heads, c.linear_key_dim
        n = x.shape[0]
        q, k, v = jnp.split(mixed, [c.key_dim, 2 * c.key_dim], axis=-1)
        q = _gd.l2_normalize(q.reshape(n, H, dk)) * dk ** -0.5
        k = _gd.l2_normalize(k.reshape(n, H, dk))
        ab = x @ layer["w_ab"].astype(ad)
        f32 = jnp.float32           # the offsets are added in float32
        g, beta = _gd.gates(
            ab[:, :H], ab[:, H:], c.a_log_init + layer["a_log"].astype(f32),
            c.dt_bias_init + layer["dt_bias"].astype(f32),
            c.allow_neg_eigval)
        return (q.astype(ad), k.astype(ad),
                v.reshape(n, H, c.linear_value_dim), g, beta)

    @R.region(R.MIXER_OUT)
    def _out(self, layer: Params, x, o):
        """Heads' outputs o (n, H, dv): normed a head, gated by SiLU of a
        projection of the layer's input x (n, e), through W_o."""
        c = self.config
        ad = c.activation_dtype
        z = (x @ layer["w_z"].astype(ad)).reshape(o.shape)
        o = rms_norm_reference(o.astype(jnp.float32), layer["o_norm"],
                               c.norm_eps)
        y = (o * jax.nn.silu(z.astype(jnp.float32))).astype(ad)
        return y.reshape(x.shape[0], -1) @ layer["wo"].astype(ad)

    def _seq(self, layer: Params, x, true_len=None):
        """The mixer over one sequence x (s, e). With a `true_len` (a
        prefill's padded bucket) through the rule's prefill, the chunk
        kernel where there is one, which stops at the prompt's true length
        inside its bucket; without, through the plain chunked form, which
        JAX differentiates. Returns (the output after W_o (s, e), the
        state at the sequence's end (H, dk, dv) float32, the convolution's
        tail)."""
        c = self.config
        s = x.shape[0]
        chunked, prefill, _ = self._rule()
        with R.region(R.MIXER_IN):
            mixed, tail = causal_conv(
                x @ layer["w_qkv"].astype(c.activation_dtype),
                layer["conv"], true_len)
            q, k, v, g, beta = self._inputs(layer, x, mixed)
            pad = -s % c.chunk              # whole chunks; padding is inert
            q, k, v, g, beta = (
                jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).swapaxes(
                    0, 1)
                for a in (q, k, v, g, beta))
        with R.region(R.MIXER_CORE):
            if true_len is None:
                o, state = chunked(q, k, v, g, beta, chunk=c.chunk)
            else:
                o, state = prefill(q, k, v, g, beta, true_len, c.chunk)
            o = o.swapaxes(0, 1)[:s]
        return self._out(layer, x, o), state, tail

    # ------------------------------------------------------- forwards
    def hidden(self, layer: Params, h, at: Walk):
        return jax.vmap(lambda seq: self._seq(layer, seq)[0])(h)

    def prefill(self, layer: Params, h, pools: Cache, li: int, at: Walk):
        """Scanned from a zero state to `true_len`, the state and the tail
        written whole into the sequence's slot."""
        c = self.config
        out, state, tail = self._seq(layer, h, at.true_len)
        with R.region(R.MIXER_CORE):
            # (H, dk, dv) -> the pool's (dk, H x dv)
            state = state.transpose(1, 0, 2).reshape(
                c.linear_key_dim, c.value_dim)
            return out, write_slot(pools, li, at.slot, state, tail)

    def decode_step(self, layer: Params, h, pools: Cache, li: int,
                    at: Walk):
        """The slots of active lanes updated in place (`conv_tail_step`,
        then the rule's step); every other left alone."""
        with R.region(R.MIXER_IN):
            mixed, tail = conv_tail_step(
                h @ layer["w_qkv"].astype(self.config.activation_dtype),
                layer["conv"], pools["tail"], li, at.slot)
        q, k, v, g, beta = self._inputs(layer, h, mixed)
        _, _, step = self._rule()
        with R.region(R.MIXER_CORE):
            o, state = step(q, k, v, g, beta, pools["state"], li, at.slot)
        return self._out(layer, h, o), {"tail": tail, "state": state}


class HybridDelta(PagedDecoder):
    """Functional model bundle for one HybridDeltaConfig: `init`, `apply`
    / `loss` (the plain chunked form, differentiated by JAX), and what a
    serving engine asks a model for (`models.paged.PagedDecoder`)."""

    no_mesh = "heads and the state pools are not sharded over chips yet"
    pages_by_count = True

    def __init__(self, config: HybridDeltaConfig, mesh=None):
        super().__init__(config, mesh)
        c = config
        self.attention = NormedAttention(
            c.d_model, c.n_heads, c.n_kv_heads, c.head_dim,
            c.activation_dtype, norm=self._norm)
        self.linear = GatedDelta(c, c.linear_heads)
        # blocks are post-norm: a mixer reads the stream as it is
        rows = {FULL: Layer((self.attention,), None),
                LINEAR: Layer((self.linear,), None)}
        self._lay([self.attention, self.linear],
                  [rows[kind] for kind in c.layer_types])

    # ------------------------------------------------------------ init
    def layer_shapes(self, i: int) -> Dict[str, Tuple[tuple, float]]:
        """Zeros are a norm's scale w, the layer multiplying by 1 + w, and
        `a_log`, `dt_bias`, offsets from the config's initial values."""
        c = self.config
        e = c.d_model
        std = 0.02
        out_std = std / math.sqrt(2 * c.n_layers)
        mixer, = self.layers[i].mixers
        return {**mixer.shapes(std, out_std),
                "attn_norm": ((e,), 0.0), "mlp_norm": ((e,), 0.0),
                "gate": ((e, c.d_ff), std), "up": ((e, c.d_ff), std),
                "down": ((c.d_ff, e), out_std)}

    # --------------------------------------------------------- pieces
    def _add(self, row: Layer, layer: Params, x, outs):
        with R.region(R.NORM):      # a post-norm and its residual addition
            return x + self._norm(outs[0], layer["attn_norm"])

    def _block_ffn(self, layer: Params, x, valid=None,
                   norm: str = "mlp_norm"):
        y = swiglu(x, layer["gate"], layer["up"], layer["down"])
        with R.region(R.NORM):
            return x + self._norm(y, layer[norm]), None
