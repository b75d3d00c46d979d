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

**Two kinds of cache behind one page table.** A full layer keeps every
position: pools `"k"`, `"v"` of `(full layers, num_pages, page, kv x hd)`,
logical page j of a sequence at its table's entry j. A linear layer keeps
a sequence the same bytes at 100 positions and at 3,000: pools `"state"`
`(linear layers, slots + 1, dk, H x dv)` float32 and `"tail"` (the
convolution's last `width - 1` inputs, `(linear layers, slots + 1, (width
- 1) x channels)`), a sequence's at the slot its **first table entry**
names. That entry is a page of the allocator's fixed class
(`serve/llm/kv_cache.py`: it names a state of any shape the model holds
and prices, here a delta rule's; `HybridSSMMoE` keeps a selective scan's
the same way), ids `0 .. slots - 1`, one a sequence, which the full
layers' pools back like any page: one table serves both kinds and nothing
is keyed by lane. `prefill` scans a prompt from a zero state
(`gated_delta_prefill`: the chunk kernel, which stops at the prompt's true
length inside its bucket) and writes the slot whole, so a slot that is
reused holds nothing of its last owner; `decode_step` updates the slots of
active lanes in place (`gated_delta_step`) and leaves every other alone.
The pools' last slot is nobody's: where an inactive lane's block goes.

Given a mesh the class refuses: neither the heads nor the state pools are
sharded over chips yet (PERF.md section 7).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import gated_delta as _gd
from ray_tpu.ops import paged_attention as _paged
from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.losses import softmax_cross_entropy
from ray_tpu.ops.norms import rms_norm, rms_norm_reference

Params = Dict[str, Any]
Cache = Dict[str, Any]

LINEAR, FULL = "linear_attention", "full_attention"
# prefill's flash blocks, as `gqa_window_moe.FULL_BLOCKS`
FULL_BLOCKS = (1024, 1024)


@dataclasses.dataclass(frozen=True)
class HybridDeltaConfig:
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

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def parameter_dtype(self):
        return jnp.dtype(self.param_dtype)


def tiny_hybrid_delta(vocab_size: int = 256) -> HybridDeltaConfig:
    """CI/debug model: every mechanism at a size the CPU runs in seconds:
    two periods of three linear layers and a full one, 4 heads of 8 / 16,
    chunks of 8."""
    return HybridDeltaConfig(
        vocab_size=vocab_size, d_model=64, n_heads=4, n_kv_heads=4,
        layer_types=(LINEAR, LINEAR, LINEAR, FULL) * 2, linear_heads=4,
        linear_key_dim=8, linear_value_dim=16, chunk=8, d_ff=128,
        max_seq_len=256, dtype="float32", param_dtype="float32")


class HybridDelta:
    """Functional model bundle for one HybridDeltaConfig: `init`, `apply`
    / `loss` (the plain chunked form, differentiated by JAX), and what a
    serving engine asks a model for (`init_cache`, `prefill`,
    `decode_step`, `cache_page_bytes`, `fixed_pages`, `fixed_step_counts`,
    `prefill_counts`, `decode_attention`, `step_stats`, `cache_stats`)."""

    def __init__(self, config: HybridDeltaConfig, mesh=None):
        if mesh is not None:
            raise NotImplementedError(
                "HybridDelta runs on one device and takes no mesh: heads "
                "and the state pools are not sharded over chips yet")
        self.config = config

    # ------------------------------------------------------------ init
    def layer_shapes(self, i: int) -> Dict[str, Tuple[tuple, float]]:
        """(shape, init std) of layer i's leaves; std 0 means zeros (a
        norm scale w, the layer multiplying by 1 + w; `a_log`, `dt_bias`,
        offsets from the config's initial values)."""
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

    def param_count(self) -> int:
        c = self.config
        return (2 * c.vocab_size * c.d_model + c.d_model + sum(
            math.prod(shape) for i in range(c.n_layers)
            for shape, _ in self.layer_shapes(i).values()))

    def init(self, key: jax.Array) -> Params:
        c = self.config
        pd = c.parameter_dtype

        def fill(key, shapes):
            keys = jax.random.split(key, len(shapes))
            return {name: (jax.random.normal(k, shape, jnp.float32)
                           * std).astype(pd) if std else jnp.zeros(shape, pd)
                    for k, (name, (shape, std)) in zip(keys,
                                                       shapes.items())}

        keys = jax.random.split(key, c.n_layers + 1)
        top = fill(keys[-1], {
            "embed": ((c.vocab_size, c.d_model), 0.02),
            "lm_head": ((c.d_model, c.vocab_size), 0.02)})
        return {**top, "final_norm": jnp.zeros((c.d_model,), pd),
                "layers": [fill(keys[i], self.layer_shapes(i))
                           for i in range(c.n_layers)]}

    # --------------------------------------------------------- pieces
    def _norm(self, x, w):
        return rms_norm(x, w, self.config.norm_eps, None)

    def _mlp(self, layer: Params, x):
        ad = self.config.activation_dtype
        gate = jax.nn.silu(x @ layer["gate"].astype(ad))
        return (gate * (x @ layer["up"].astype(ad))) @ layer[
            "down"].astype(ad)

    def _close(self, layer: Params, x, mixed):
        """The rest of a block after its mixer: both post-norm adds."""
        x = x + self._norm(mixed, layer["attn_norm"])
        return x + self._norm(self._mlp(layer, x), layer["mlp_norm"])

    def _full_qkv(self, layer: Params, x):
        """x (..., e) -> q (..., heads, hd), k, v (..., kv heads, hd), q
        and k normed over their whole width first."""
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
        qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        out = flash_attention(qt, kt, vt, causal=True,
                              block_q=FULL_BLOCKS[0], block_k=FULL_BLOCKS[1])
        out = out.transpose(0, 2, 1, 3).reshape(x.shape)
        return out @ layer["wo"].astype(self.config.activation_dtype), k, v

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
        mixed, tail = _gd.causal_conv(
            x @ layer["w_qkv"].astype(c.activation_dtype), layer["conv"],
            true_len)
        q, k, v, g, beta = self._linear_inputs(layer, x, mixed)
        pad = -s % c.chunk                  # whole chunks; padding is inert
        q, k, v, g, beta = (
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).swapaxes(0, 1)
            for a in (q, k, v, g, beta))
        if true_len is None:
            o, state = _gd.gated_delta_chunked(q, k, v, g, beta,
                                               chunk=c.chunk)
        else:
            o, state = _gd.gated_delta_prefill(q, k, v, g, beta, true_len,
                                               c.chunk)
        o = o.swapaxes(0, 1)[:s]
        return self._linear_out(layer, x, o), state, tail

    # --------------------------------------------------------- forward
    def hidden(self, params: Params, tokens: jax.Array) -> jax.Array:
        """tokens (b, s) -> hidden states after the final norm."""
        c = self.config
        x = params["embed"].astype(c.activation_dtype)[tokens]
        for i, layer in enumerate(params["layers"]):
            if c.layer_types[i] == FULL:
                mixed = self._full_seq(layer, x)[0]
            else:
                mixed = jax.vmap(
                    lambda seq: self._linear_seq(layer, seq)[0])(x)
            x = self._close(layer, x, mixed)
        return self._norm(x, params["final_norm"])

    def apply(self, params: Params, tokens: jax.Array) -> jax.Array:
        """tokens (b, s) int32 -> logits (b, s, vocab) in f32."""
        x = self.hidden(params, tokens)
        head = params["lm_head"].astype(self.config.activation_dtype)
        return (x @ head).astype(jnp.float32)

    def loss(self, params: Params, batch: Dict[str, jax.Array]):
        """Causal LM loss of batch["tokens"] (b, s), as `MLAMoE.loss`. The
        linear layers run the plain chunked form here: the chunk kernel
        has no backward (PERF.md section 7)."""
        tokens = batch["tokens"]
        mask = batch.get("loss_mask")
        logits = self.apply(params, tokens)[:, :-1]
        if mask is not None:
            mask = mask[:, 1:]
        loss, _ = softmax_cross_entropy(logits, tokens[:, 1:], mask=mask)
        return loss

    # ------------------------------------------------ what an engine asks
    def fixed_pages(self, page_size: int) -> int:
        """Pages of the allocator's fixed class a sequence holds for ever:
        one, its first table entry, which names its state slot."""
        return int(bool(self.config.linear_layers))

    def state_bytes(self, dtype=None) -> int:
        """Bytes the linear layers keep of one sequence, whatever its
        length: a float32 state and the convolution's tail a layer."""
        c = self.config
        dt = jnp.dtype(dtype or c.activation_dtype)
        return len(c.linear_layers) * (
            c.linear_key_dim * c.value_dim * 4
            + (c.conv_width - 1) * c.conv_channels * dt.itemsize)

    def fixed_step_counts(self, length: int, page_size: int,
                          kernel: bool = True) -> Dict[str, int]:
        """What a lane's fixed part costs a decode step, by the names the
        engine's span carries: its state slot, and the bytes the linear
        layers move for it (state and tail, read and written), whatever
        its `length`."""
        return {"state_slots": 1, "state_bytes": 2 * self.state_bytes()}

    def prefill_counts(self, tokens: int, bucket: int) -> Dict[str, int]:
        """What a prefill of `tokens` in its `bucket` runs, for the
        engine's span: the chunks a linear layer scans (those that hold
        the prompt; the kernel skips the bucket's others)."""
        return {"scan_chunks": -(-tokens // self.config.chunk)}

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
            "tail": jnp.zeros((lin, slots,
                               (c.conv_width - 1) * c.conv_channels), dt)})
        return make()

    def cache_page_bytes(self, page_size: int, tp_shards: int = 1,
                         dtype=None, fixed: bool = False) -> int:
        """Bytes one page costs: keys and values of the full layers for a
        page of the pool `num_pages` counts; what the linear layers keep
        of a sequence (`fixed`), which its fixed-class page costs
        besides."""
        c = self.config
        if fixed:
            return self.state_bytes(dtype)
        dt = jnp.dtype(dtype or c.activation_dtype)
        return (2 * len(c.full_layers) * page_size
                * (c.kv_dim // max(1, tp_shards)) * dt.itemsize)

    def decode_attention(self, page_size: int, dtype=None) -> str:
        """Which attention a `decode_step` traced here holds: the kernel
        of each layer kind, or "einsum" (the full layers gather)."""
        c = self.config
        if not _paged.uses_kernel(c.head_dim, page_size,
                                  dtype or c.activation_dtype):
            return "einsum"
        step = (_gd.KERNEL_STEP if _gd.uses_step_kernel(
            c.linear_heads, c.linear_key_dim, c.linear_value_dim)
            else "gated_delta_gather")
        return "+".join([_paged.KERNEL_PAGED_DECODE] * bool(c.full_layers)
                        + [step] * bool(c.linear_layers))

    def walk_block_pages(self, page_size: int, max_pages: int) -> int:
        """Pages a block of the full layers' walk holds over tables of
        `max_pages`, asked what the kernel asks (a layer's page of keys
        and values)."""
        return _paged.walk_block_pages(
            self.cache_page_bytes(page_size)
            // max(1, len(self.config.full_layers)), page_size, max_pages)

    def step_stats(self, cache: Cache) -> Dict[str, jax.Array]:
        return {}

    def cache_stats(self, cache: Cache) -> Dict[str, Any]:
        return {}

    def prefill(self, params: Params, tokens: jax.Array, true_len,
                page_table: jax.Array, cache: Cache,
                page_size: int) -> Tuple[jax.Array, Cache]:
        """One padded prompt, as `models.decode.prefill`: a full layer
        through the flash kernel, its keys and values written as whole
        pages in place (donate the cache); a linear layer scanned from a
        zero state to `true_len`, its state and tail written whole into
        the slot the table's first entry names. Returns (last-position
        logits (vocab,) f32, cache)."""
        c = self.config
        ad = c.activation_dtype
        pools = dict(cache)
        num_pages, slots = pools["k"].shape[1], pools["state"].shape[1] - 1
        s = tokens.shape[0]
        x = params["embed"].astype(ad)[tokens]                  # (s, e)
        n = -(-s // page_size)
        j = jnp.arange(n)
        held = -(-true_len // page_size)         # pages the prompt fills
        ids = jnp.where(j < held, jnp.take(page_table, j, mode="clip"),
                        num_pages)
        slot = page_table[0]
        slot = jnp.where((slot >= 0) & (slot < slots), slot, slots + 1)

        def pages(a):
            a = jnp.pad(a[0].reshape(s, c.kv_dim),
                        ((0, n * page_size - s), (0, 0)))
            return a.reshape(n, page_size, c.kv_dim)

        for i, layer in enumerate(params["layers"]):
            if c.layer_types[i] == FULL:
                li = c.full_layers.index(i)
                mixed, k, v = self._full_seq(layer, x[None])
                mixed = mixed[0]
                for name, a in (("k", k), ("v", v)):
                    pools[name] = pools[name].at[li, ids].set(
                        pages(a).astype(pools[name].dtype), mode="drop")
            else:
                li = c.linear_layers.index(i)
                mixed, state, tail = self._linear_seq(layer, x, true_len)
                # (H, dk, dv) -> the pool's (dk, H x dv)
                state = state.transpose(1, 0, 2).reshape(
                    c.linear_key_dim, c.value_dim)
                pools["state"] = pools["state"].at[li, slot].set(
                    state, mode="drop")
                pools["tail"] = pools["tail"].at[li, slot].set(
                    tail.reshape(-1).astype(pools["tail"].dtype),
                    mode="drop")
            x = self._close(layer, x, mixed)
        x = self._norm(x, params["final_norm"])
        last = jnp.take(x, true_len - 1, axis=0)
        logits = (last @ params["lm_head"].astype(ad)).astype(jnp.float32)
        return logits, pools

    def decode_step(self, params: Params, cache: Cache, tokens: jax.Array,
                    positions: jax.Array, page_tables: jax.Array,
                    active: jax.Array,
                    page_size: int) -> Tuple[jax.Array, Cache]:
        """Advance a padded batch by one token each, as
        `models.decode.decode_step`. An inactive lane, or one whose table
        is unassigned, writes no page, no state and no tail. Returns
        (logits (B, vocab) f32, cache) — donate the cache."""
        c = self.config
        ad = c.activation_dtype
        pools = dict(cache)
        num_pages, slots = pools["k"].shape[1], pools["state"].shape[1] - 1
        B = tokens.shape[0]
        x = params["embed"].astype(ad)[tokens]                  # (B, e)
        lengths = jnp.where(active, positions + 1, 0)
        logical = positions // page_size
        offset = positions % page_size
        page = jnp.take_along_axis(page_tables, logical[:, None],
                                   axis=1)[:, 0]
        page = jnp.where(active & (page >= 0), page, num_pages)
        first = page_tables[:, 0]
        slot = jnp.where(active & (first >= 0) & (first < slots), first, -1)
        tail_at = jnp.where(slot >= 0, slot, slots + 1)     # -1: dropped
        for i, layer in enumerate(params["layers"]):
            if c.layer_types[i] == FULL:
                li = c.full_layers.index(i)
                q, k, v = self._full_qkv(layer, x)
                for name, a in (("k", k), ("v", v)):
                    pools[name] = pools[name].at[li, page, offset].set(
                        a.reshape(B, c.kv_dim).astype(pools[name].dtype),
                        mode="drop")
                out = _paged.paged_decode_attention(
                    q.astype(pools["k"].dtype), pools["k"], pools["v"], li,
                    page_tables, lengths)
                mixed = out.astype(ad).reshape(B, -1) @ layer["wo"].astype(
                    ad)
            else:
                li = c.linear_layers.index(i)
                tail = pools["tail"][li, jnp.clip(slot, 0, slots)].reshape(
                    B, c.conv_width - 1, c.conv_channels)
                mixed, tail = _gd.conv_step(
                    x @ layer["w_qkv"].astype(ad), tail, layer["conv"])
                pools["tail"] = pools["tail"].at[li, tail_at].set(
                    tail.reshape(B, -1), mode="drop")
                q, k, v, g, beta = self._linear_inputs(layer, x, mixed)
                o, pools["state"] = _gd.gated_delta_step(
                    q, k, v, g, beta, pools["state"], li, slot)
                mixed = self._linear_out(layer, x, o)
            x = self._close(layer, x, mixed)
        x = self._norm(x, params["final_norm"])
        logits = (x @ params["lm_head"].astype(ad)).astype(jnp.float32)
        return logits, pools
