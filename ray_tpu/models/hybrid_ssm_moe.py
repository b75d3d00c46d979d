"""A decoder whose layers are one mixer each, of three kinds named layer by
layer by the config's pattern: a state-space mixer (a selective scan: a
recurrent state of fixed size a sequence), a mixture of experts that live in
a latent narrower than the stream, or grouped-query attention (the
`nemotron_h` family's hybrid of the three), on the ops the other classes run
on and behind the same serving engine.

Every layer is a pre-norm block, `x' = x + Mixer(N(x))`, `N` an RMSNorm of
its own; there is no second sub-layer. A final norm before the untied head.

`M`, a **state-space** mixer (`models/ssm.py` has its mathematics and its
two pools; `ops.ssd` the scan): H heads of width P in G groups, a state of
N numbers a channel, no scale on `W_in`'s columns, the gate before the
norm.

`*`, **attention**: grouped-query softmax attention, no bias and no rotary
embedding (position comes from the state-space layers).

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

**Two kinds of cache behind one page table**, as `HybridDelta` holds them:
pools `"k"`, `"v"` `(attention layers, num_pages, page, kv heads x head
dim)` for the attention layers alone; for the state-space layers `"state"`
and `"tail"` (`models.ssm.SSMMixer`), a sequence's at the slot its first
table entry names (`paged.StateSlots`). A decode step's recurrence is the
step kernel where its blocks tile the state (`ops.ssd.step_columns`: whole
groups side by side where a group fits a block, two of this family's eight
a grid step; part of one group where it does not). Beside them
`paged.ExpertCounts`' two entries.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import gqa
from ray_tpu.models import regions as R
from ray_tpu.models.config import ConfigDtypes
from ray_tpu.models.moe import dropless_moe_ffn
from ray_tpu.models.paged import (Cache, ExpertCounts, PagedDecoder, Params,
                                  StateSlots, decode_lanes,
                                  decode_state_slots,
                                  prefill_page_ids, prefill_state_slot)
from ray_tpu.models.ssm import SSMDims, SSMMixer
from ray_tpu.ops import paged_attention as _paged
from ray_tpu.ops import ssd as _ssd

# a layer's kind, by the letters of the family's `hybrid_override_pattern`
SSM, EXPERTS, ATTENTION = "M", "E", "*"
# the region of a layer's residual addition, by its kind
_CLOSES = {SSM: R.MIXER_OUT, EXPERTS: R.FFN, ATTENTION: R.ATTN_OUT}


@dataclasses.dataclass(frozen=True)
class HybridSSMMoEConfig(SSMDims, ConfigDtypes):
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


class HybridSSMMoE(SSMMixer, StateSlots, ExpertCounts, PagedDecoder):
    """Functional model bundle for one HybridSSMMoEConfig: `init`, `apply`
    / `loss` (the plain chunked scan, differentiated by JAX), and what a
    serving engine asks a model for (`models.paged.PagedDecoder`)."""

    no_mesh = ("neither the state pools nor the experts' exchange over "
               "chips have been built")

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
            q = c.n_heads * c.head_dim
            return {"norm": ((e,), 0.0), "wq": ((e, q), std),
                    "wk": ((e, c.kv_dim), std), "wv": ((e, c.kv_dim), std),
                    "wo": ((q, e), out_std)}
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
        return {"norm": ((e,), 0.0), **self.ssm_shapes(std, out_std)}

    # --------------------------------------------------------- pieces
    def _attn_seq(self, layer: Params, u):
        """Causal attention over whole sequences u (b, s, e). Returns (the
        output after W_o, k, v (b, s, kv heads, hd))."""
        c = self.config
        q, k, v = gqa.qkv(layer, u, c.n_heads, c.n_kv_heads, c.head_dim,
                          c.activation_dtype)
        out = gqa.attend_seq(q, k, v)
        with R.region(R.ATTN_OUT):
            out = out.reshape(*u.shape[:-1], -1)
            return out @ layer["wo"].astype(c.activation_dtype), k, v

    def _experts(self, layer: Params, u, valid=None):
        """An expert layer's mixer on the normed stream u (n, e): this
        chip's experts' part through the latent, and the shared expert.
        Returns (the mixer's output, the routed part's counts)."""
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

    # --------------------------------------------------------- forward
    def hidden(self, params: Params, tokens: jax.Array) -> jax.Array:
        """tokens (b, s) -> hidden states after the final norm."""
        c = self.config
        b, s = tokens.shape
        x = self._embed(params, tokens)
        for i, layer in enumerate(params["layers"]):
            u = self._norm(x, layer["norm"])
            kind = c.layer_types[i]
            if kind == ATTENTION:
                mixed = self._attn_seq(layer, u)[0]
            elif kind == EXPERTS:
                mixed = self._experts(layer, u.reshape(b * s, -1))[
                    0].reshape(x.shape)
            else:
                mixed = jax.vmap(
                    lambda seq: self._ssm_seq(layer, seq)[0])(u)
            with R.region(_CLOSES[kind]):
                x = x + mixed
        return self._final_norm(params, x)

    # ------------------------------------------------ what an engine asks
    def state_bytes(self, dtype=None) -> int:
        """Bytes the state-space layers keep of one sequence, whatever its
        length."""
        return len(self.config.of_kind(SSM)) * self.ssm_layer_bytes(dtype)

    def init_cache(self, num_pages: int, page_size: int, dtype=None,
                   fixed_pages: int = 0) -> Cache:
        """`num_pages` pages in the attention layers' pools; `fixed_pages`
        state slots (the allocator's fixed class, one a sequence) and one
        more, nobody's, in the state-space layers'."""
        c = self.config
        dt = dtype or c.activation_dtype
        kv = (len(c.of_kind(ATTENTION)), num_pages, page_size, c.kv_dim)
        make = jax.jit(lambda: {
            "k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
            **self.ssm_pools(len(c.of_kind(SSM)), fixed_pages + 1, dt),
            **self._zero_counts()})
        return make()

    @property
    def expert_load_shape(self) -> Tuple[int, int]:
        return len(self.config.of_kind(EXPERTS)), self.config.held[1]

    def page_bytes(self, page_size: int, tp_shards: int = 1,
                   dtype=None) -> int:
        """Keys and values of the attention layers."""
        c = self.config
        return len(c.of_kind(ATTENTION)) * gqa.layer_page_bytes(
            c.kv_dim, page_size, dtype or c.activation_dtype, tp_shards)

    def decode_attention(self, page_size: int, dtype=None) -> str:
        """The kernel of each layer kind, or "einsum"."""
        c = self.config
        return gqa.decode_kernels(
            c.head_dim, page_size, dtype or c.activation_dtype,
            [(_paged.KERNEL_PAGED_DECODE, c.of_kind(ATTENTION)),
             (self.ssm_step_name(), c.of_kind(SSM))])

    def walk_block_pages(self, page_size: int, max_pages: int) -> int:
        """Of the attention layers' walk."""
        c = self.config
        return gqa.walk_block_pages(c.kv_dim, page_size, max_pages,
                                    c.activation_dtype)

    def prefill(self, params: Params, tokens: jax.Array, true_len,
                page_table: jax.Array, cache: Cache,
                page_size: int) -> Tuple[jax.Array, Cache]:
        """An attention layer through the flash kernel, its keys and
        values written as whole pages in place; a state-space layer
        scanned from a zero state to `true_len`, its state and tail
        written whole into the slot the table's first entry names; padding
        past `true_len` given to no expert."""
        c = self.config
        pools = dict(cache)
        num_pages, slots = pools["k"].shape[1], pools["state"].shape[1] - 1
        s = tokens.shape[0]
        x = self._embed(params, tokens)                         # (s, e)
        ids = prefill_page_ids(page_table, true_len, s, num_pages, page_size)
        slot = prefill_state_slot(page_table, slots)
        with R.region(R.CACHE):
            valid = jnp.arange(s) < true_len
        for i, layer in enumerate(params["layers"]):
            u = self._norm(x, layer["norm"])
            kind = c.layer_types[i]
            if kind == ATTENTION:
                li = c.of_kind(ATTENTION).index(i)
                mixed, k, v = self._attn_seq(layer, u[None])
                mixed = mixed[0]
                pools.update(gqa.write_prompt(pools, ("k", "v"), li, ids,
                                              k, v))
            elif kind == EXPERTS:
                mixed, _ = self._experts(layer, u, valid)
            else:
                li = c.of_kind(SSM).index(i)
                mixed, state, tail = self._ssm_seq(layer, u, true_len)
                pools.update(self._write_slot(pools, li, slot, state,
                                              tail))
            with R.region(_CLOSES[kind]):
                x = x + mixed
        return self._logits(params, x, true_len), pools

    def decode_step(self, params: Params, cache: Cache, tokens: jax.Array,
                    positions: jax.Array, page_tables: jax.Array,
                    active: jax.Array,
                    page_size: int) -> Tuple[jax.Array, Cache]:
        """An inactive lane, or one whose table is unassigned, writes no
        page, no state and no tail, and is given to no expert."""
        c = self.config
        ad = c.activation_dtype
        pools = dict(cache)
        num_pages, slots = pools["k"].shape[1], pools["state"].shape[1] - 1
        B = tokens.shape[0]
        x = self._embed(params, tokens)                         # (B, e)
        page, offset, lengths = decode_lanes(positions, page_tables, active,
                                             num_pages, page_size)
        slot = decode_state_slots(page_tables, active, slots)
        load, sums = pools["moe_load"], self._step_sums()
        for i, layer in enumerate(params["layers"]):
            u = self._norm(x, layer["norm"])
            kind = c.layer_types[i]
            if kind == ATTENTION:
                li = c.of_kind(ATTENTION).index(i)
                q, k, v = gqa.qkv(layer, u, c.n_heads, c.n_kv_heads,
                                  c.head_dim, ad)
                out, written = gqa.decode_attend(
                    pools, ("k", "v"), li, page, offset, q, k, v,
                    page_tables, lengths)
                pools.update(written)
                with R.region(R.ATTN_OUT):
                    mixed = out.astype(ad).reshape(B, -1) @ layer[
                        "wo"].astype(ad)
            elif kind == EXPERTS:
                li = c.of_kind(EXPERTS).index(i)
                mixed, counts = self._experts(layer, u, active)
                with R.region(R.MOE_ROUTE):
                    load = load.at[li].add(counts["load"])
                sums = self._count_step(sums, counts)
            else:
                li = c.of_kind(SSM).index(i)
                mixed, written = self._ssm_step(layer, u, pools, li, slot)
                pools.update(written)
            with R.region(_CLOSES[kind]):
                x = x + mixed
        return self._logits(params, x), {**pools,
                                         **self._counted(load, sums)}
