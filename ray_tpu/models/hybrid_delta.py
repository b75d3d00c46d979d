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
`ops.gated_delta.tail_shape`), a sequence's at the slot its first table
entry names (`paged.StateSlots`: a page of the allocator's fixed class, which
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

from ray_tpu.models import gqa
from ray_tpu.models import regions as R
from ray_tpu.models.config import ConfigDtypes
from ray_tpu.models.moe import swiglu
from ray_tpu.models.paged import (Cache, PagedDecoder, Params, StateSlots,
                                  decode_state_slots, lane_page,
                                  prefill_page_ids_held, prefill_state_slot)
from ray_tpu.ops import gated_delta as _gd
from ray_tpu.ops import paged_attention as _paged
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


class HybridDelta(StateSlots, PagedDecoder):
    """Functional model bundle for one HybridDeltaConfig: `init`, `apply`
    / `loss` (the plain chunked form, differentiated by JAX), and what a
    serving engine asks a model for (`models.paged.PagedDecoder`)."""

    no_mesh = "heads and the state pools are not sharded over chips yet"

    # ------------------------------------------------------------ init
    def layer_shapes(self, i: int) -> Dict[str, Tuple[tuple, float]]:
        """Zeros are a norm's scale w, the layer multiplying by 1 + w, and
        `a_log`, `dt_bias`, offsets from the config's initial values."""
        c = self.config
        e = c.d_model
        std = 0.02
        out_std = std / math.sqrt(2 * c.n_layers)
        ffn = {"attn_norm": ((e,), 0.0), "mlp_norm": ((e,), 0.0),
               "gate": ((e, c.d_ff), std), "up": ((e, c.d_ff), std),
               "down": ((c.d_ff, e), out_std)}
        if c.layer_types[i] == FULL:
            return {"wq": ((e, e), std), "wk": ((e, c.kv_dim), std),
                    "wv": ((e, c.kv_dim), std), "wo": ((e, e), out_std),
                    "q_norm": ((e,), 0.0), "k_norm": ((c.kv_dim,), 0.0),
                    **ffn}
        H = c.linear_heads
        return {"w_qkv": ((e, c.conv_channels), std),
                "w_z": ((e, c.value_dim), std), "w_ab": ((e, 2 * H), std),
                "conv": ((c.conv_width, c.conv_channels), std),
                "a_log": ((H,), 0.0), "dt_bias": ((H,), 0.0),
                "o_norm": ((c.linear_value_dim,), 0.0),
                "wo": ((c.value_dim, e), out_std), **ffn}

    # --------------------------------------------------------- pieces
    def _close(self, layer: Params, x, mixed):
        """The rest of a block after its mixer: both post-norm adds."""
        with R.region(R.NORM):      # a post-norm and its residual addition
            x = x + self._norm(mixed, layer["attn_norm"])
        y = swiglu(x, layer["gate"], layer["up"], layer["down"])
        with R.region(R.NORM):
            return x + self._norm(y, layer["mlp_norm"])

    @R.region(R.ATTN_IN)
    def _full_qkv(self, layer: Params, x):
        """x (..., e) -> q (..., heads, hd), k, v (..., kv heads, hd), q
        and k normed over their whole width first, all three before any
        is split (`gqa.qkv` splits each as it is projected: another text)."""
        c = self.config
        ad = c.activation_dtype
        q = self._norm(x @ layer["wq"].astype(ad), layer["q_norm"])
        k = self._norm(x @ layer["wk"].astype(ad), layer["k_norm"])
        v = x @ layer["wv"].astype(ad)
        lead = x.shape[:-1]
        return (q.reshape(*lead, c.n_heads, c.head_dim),
                k.reshape(*lead, c.n_kv_heads, c.head_dim),
                v.reshape(*lead, c.n_kv_heads, c.head_dim))

    def _full_seq(self, layer: Params, x):
        """Causal attention over whole sequences x (b, s, e). Returns
        (the output after W_o, k, v (b, s, kv heads, hd))."""
        q, k, v = self._full_qkv(layer, x)
        out = gqa.attend_seq(q, k, v)
        with R.region(R.ATTN_OUT):
            out = out.reshape(x.shape)
            return (out @ layer["wo"].astype(self.config.activation_dtype),
                    k, v)

    @R.region(R.MIXER_IN)
    def _linear_inputs(self, layer: Params, x, mixed):
        """What the recurrence takes of positions x (n, e) whose
        convolved channels are `mixed` (n, channels): q, k (n, H, dk) and
        v (n, H, dv) in the activations' dtype, g, beta (n, H) float32."""
        c = self.config
        ad = c.activation_dtype
        H, dk = c.linear_heads, c.linear_key_dim
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
    def _linear_out(self, layer: Params, x, o):
        """Heads' outputs o (n, H, dv): normed a head, gated by SiLU of a
        projection of the layer's input x (n, e), through W_o."""
        c = self.config
        ad = c.activation_dtype
        z = (x @ layer["w_z"].astype(ad)).reshape(o.shape)
        o = rms_norm_reference(o.astype(jnp.float32), layer["o_norm"],
                               c.norm_eps)
        y = (o * jax.nn.silu(z.astype(jnp.float32))).astype(ad)
        return y.reshape(x.shape[0], -1) @ layer["wo"].astype(ad)

    def _linear_seq(self, layer: Params, x, true_len=None):
        """A linear layer over one sequence x (s, e). With a `true_len`
        (a prefill's padded bucket) through `gated_delta_prefill`, the
        kernel where there is one; without, through the plain chunked
        form, which JAX differentiates. Returns (the output after W_o
        (s, e), the state at the sequence's end (H, dk, dv) float32, the
        convolution's tail)."""
        c = self.config
        s = x.shape[0]
        with R.region(R.MIXER_IN):
            mixed, tail = _gd.causal_conv(
                x @ layer["w_qkv"].astype(c.activation_dtype),
                layer["conv"], true_len)
            q, k, v, g, beta = self._linear_inputs(layer, x, mixed)
            pad = -s % c.chunk              # whole chunks; padding is inert
            q, k, v, g, beta = (
                jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).swapaxes(
                    0, 1)
                for a in (q, k, v, g, beta))
        with R.region(R.MIXER_CORE):
            if true_len is None:
                o, state = _gd.gated_delta_chunked(q, k, v, g, beta,
                                                   chunk=c.chunk)
            else:
                o, state = _gd.gated_delta_prefill(q, k, v, g, beta,
                                                   true_len, c.chunk)
            o = o.swapaxes(0, 1)[:s]
        return self._linear_out(layer, x, o), state, tail

    # --------------------------------------------------------- forward
    def hidden(self, params: Params, tokens: jax.Array) -> jax.Array:
        """tokens (b, s) -> hidden states after the final norm."""
        c = self.config
        x = self._embed(params, tokens)
        for i, layer in enumerate(params["layers"]):
            if c.layer_types[i] == FULL:
                mixed = self._full_seq(layer, x)[0]
            else:
                mixed = jax.vmap(
                    lambda seq: self._linear_seq(layer, seq)[0])(x)
            x = self._close(layer, x, mixed)
        return self._final_norm(params, x)

    # ------------------------------------------------ what an engine asks
    def state_bytes(self, dtype=None) -> int:
        """Bytes the linear layers keep of one sequence, whatever its
        length: a float32 state and the convolution's tail a layer, as
        the pools hold them (`tail_shape`: whole tiles of rows)."""
        c = self.config
        dt = jnp.dtype(dtype or c.activation_dtype)
        return len(c.linear_layers) * (
            c.linear_key_dim * c.value_dim * 4
            + math.prod(_gd.tail_shape(c.conv_width, c.conv_channels))
            * dt.itemsize)

    def init_cache(self, num_pages: int, page_size: int, dtype=None,
                   fixed_pages: int = 0) -> Cache:
        """`num_pages` pages in the full layers' pools; `fixed_pages`
        state slots (the allocator's fixed class, one a sequence) and one
        more, nobody's, in the linear layers'."""
        c = self.config
        dt = dtype or c.activation_dtype
        full = (len(c.full_layers), num_pages, page_size, c.kv_dim)
        lin, slots = len(c.linear_layers), fixed_pages + 1
        make = jax.jit(lambda: {
            "k": jnp.zeros(full, dt), "v": jnp.zeros(full, dt),
            "state": jnp.zeros((lin, slots, c.linear_key_dim, c.value_dim),
                               jnp.float32),
            "tail": jnp.zeros((lin, slots) + _gd.tail_shape(
                c.conv_width, c.conv_channels), dt)})
        return make()

    def page_bytes(self, page_size: int, tp_shards: int = 1,
                   dtype=None) -> int:
        """Keys and values of the full layers."""
        c = self.config
        return len(c.full_layers) * gqa.layer_page_bytes(
            c.kv_dim, page_size, dtype or c.activation_dtype, tp_shards)

    def decode_attention(self, page_size: int, dtype=None) -> str:
        """The kernel of each layer kind, or "einsum"."""
        c = self.config
        step = (_gd.KERNEL_STEP if _gd.uses_step_kernel(
            c.linear_heads, c.linear_key_dim, c.linear_value_dim)
            else "gated_delta_gather")
        return gqa.decode_kernels(
            c.head_dim, page_size, dtype or c.activation_dtype,
            [(_paged.KERNEL_PAGED_DECODE, c.full_layers),
             (step, c.linear_layers)])

    def walk_block_pages(self, page_size: int, max_pages: int) -> int:
        """Of the full layers' walk."""
        c = self.config
        return gqa.walk_block_pages(c.kv_dim, page_size, max_pages,
                                    c.activation_dtype)

    def prefill(self, params: Params, tokens: jax.Array, true_len,
                page_table: jax.Array, cache: Cache,
                page_size: int) -> Tuple[jax.Array, Cache]:
        """A full layer through the flash kernel, its keys and values
        written as whole pages in place; a linear layer scanned from a
        zero state to `true_len`, its state and tail written whole into
        the slot the table's first entry names."""
        c = self.config
        pools = dict(cache)
        num_pages, slots = pools["k"].shape[1], pools["state"].shape[1] - 1
        x = self._embed(params, tokens)                         # (s, e)
        ids, _ = prefill_page_ids_held(page_table, true_len,
                                       tokens.shape[0], num_pages,
                                       page_size)
        slot = prefill_state_slot(page_table, slots)
        for i, layer in enumerate(params["layers"]):
            if c.layer_types[i] == FULL:
                li = c.full_layers.index(i)
                mixed, k, v = self._full_seq(layer, x[None])
                mixed = mixed[0]
                pools.update(gqa.write_prompt(pools, ("k", "v"), li, ids,
                                              k, v))
            else:
                li = c.linear_layers.index(i)
                mixed, state, tail = self._linear_seq(layer, x, true_len)
                with R.region(R.MIXER_CORE):
                    # (H, dk, dv) -> the pool's (dk, H x dv)
                    state = state.transpose(1, 0, 2).reshape(
                        c.linear_key_dim, c.value_dim)
                    pools.update(self._write_slot(pools, li, slot, state,
                                                  tail))
            x = self._close(layer, x, mixed)
        return self._logits(params, x, true_len), pools

    def decode_step(self, params: Params, cache: Cache, tokens: jax.Array,
                    positions: jax.Array, page_tables: jax.Array,
                    active: jax.Array,
                    page_size: int) -> Tuple[jax.Array, Cache]:
        """An inactive lane, or one whose table is unassigned, writes no
        page, no state and no tail."""
        c = self.config
        ad = c.activation_dtype
        pools = dict(cache)
        num_pages, slots = pools["k"].shape[1], pools["state"].shape[1] - 1
        B = tokens.shape[0]
        x = self._embed(params, tokens)                         # (B, e)
        # `paged.decode_lanes`' three, in the order this class's traced
        # text has always had them
        with R.region(R.CACHE):
            lengths = jnp.where(active, positions + 1, 0)
            logical = positions // page_size
            offset = positions % page_size
        page = lane_page(page_tables, logical, active, num_pages)
        slot = decode_state_slots(page_tables, active, slots)
        for i, layer in enumerate(params["layers"]):
            if c.layer_types[i] == FULL:
                li = c.full_layers.index(i)
                q, k, v = self._full_qkv(layer, x)
                out, written = gqa.decode_attend(
                    pools, ("k", "v"), li, page, offset, q, k, v,
                    page_tables, lengths)
                pools.update(written)
                with R.region(R.ATTN_OUT):
                    mixed = out.astype(ad).reshape(B, -1) @ layer[
                        "wo"].astype(ad)
            else:
                li = c.linear_layers.index(i)
                with R.region(R.MIXER_IN):
                    mixed, pools["tail"] = _gd.conv_tail_step(
                        x @ layer["w_qkv"].astype(ad), layer["conv"],
                        pools["tail"], li, slot)
                q, k, v, g, beta = self._linear_inputs(layer, x, mixed)
                with R.region(R.MIXER_CORE):
                    o, pools["state"] = _gd.gated_delta_step(
                        q, k, v, g, beta, pools["state"], li, slot)
                mixed = self._linear_out(layer, x, o)
            x = self._close(layer, x, mixed)
        return self._logits(params, x), pools
