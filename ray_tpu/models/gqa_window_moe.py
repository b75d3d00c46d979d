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

Given a mesh the class refuses: neither the experts nor the two pools are
sharded over chips yet (PERF.md section 7).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.moe import dropless_moe_ffn
from ray_tpu.ops import paged_attention as _paged
from ray_tpu.ops import rope as _rope
from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.losses import softmax_cross_entropy
from ray_tpu.ops.norms import rms_norm

Params = Dict[str, Any]
Cache = Dict[str, Any]

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
# prefill's flash blocks (block_q, block_k), read on the chip at 1k, 4k
# and 8k tokens (PERF.md, PR 35): a full layer's, as large as training's
# (8.3 ms at 8192 tokens and 48 heads against 16.9 at 512 and 84 at 128:
# a grid step costs what a small block's matmuls do); a sliding layer's
# query block of 512 reaches two key blocks of 1024 (4.3 ms at 8192 tokens
# and 64 heads against 8.9 at 256 x 256, where less is computed and masked
# but the steps are four times as many)
FULL_BLOCKS = (1024, 1024)
SLIDING_BLOCKS = (512, 1024)
# what a decode step counts over its expert layers (`Cache["moe_step"]`);
# the engine's counters take these names
STEP_COUNTS = ("moe_pairs", "moe_experts_touched", "moe_load_max")


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
class GQAWindowMoEConfig:
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

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def parameter_dtype(self):
        return jnp.dtype(self.param_dtype)


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


class GQAWindowMoE:
    """Functional model bundle for one GQAWindowMoEConfig: `init`, `apply`
    / `loss` (a plain forward, the tests' and a trainer's), and what a
    serving engine asks a model for (`init_cache`, `prefill`,
    `decode_step`, `cache_page_bytes`, `fixed_pages`, `fixed_step_counts`,
    `prefill_counts`, `decode_attention`, `step_stats`, `cache_stats`)."""

    def __init__(self, config: GQAWindowMoEConfig, mesh=None):
        if mesh is not None:
            raise NotImplementedError(
                "GQAWindowMoE runs on one device and takes no mesh: experts "
                "and the two pools are not sharded over chips yet")
        self.config = config
        self._ring_walks: Dict[int, list] = {}  # `fixed_step_counts`'s

    # ------------------------------------------------------------ init
    def layer_shapes(self, i: int) -> Dict[str, Tuple[tuple, float]]:
        """(shape, init std) of layer i's leaves; std 0 means zeros (a
        norm scale, stored as w with the layer multiplying by 1 + w)."""
        c = self.config
        e, q_dim = c.d_model, c.n_heads_per_layer[i] * c.head_dim
        std = 0.02
        out_std = std / math.sqrt(2 * c.n_layers)
        shapes = {
            "attn_norm": ((e,), 0.0),
            "wq": ((e, q_dim), std), "wk": ((e, c.kv_dim), std),
            "wv": ((e, c.kv_dim), std), "wo": ((q_dim, e), out_std),
            "wg": ((e, c.n_heads_per_layer[i]), std),   # the gate, a head
            "mlp_norm": ((e,), 0.0),
        }
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

    def _ropes(self, positions: jax.Array):
        """kind -> (cos, sin) of `positions`: both tables, once a program."""
        c = self.config
        return {FULL: c.rope_full.cos_sin(positions, c.head_dim),
                SLIDING: c.rope_sliding.cos_sin(positions, c.head_dim)}

    def _qkv(self, i: int, layer: Params, h, ropes):
        """h (..., e) -> q (..., heads, hd), k, v (..., kv heads, hd), q
        and k rotated by the layer kind's scheme."""
        c = self.config
        ad = c.activation_dtype
        cos, sin = ropes[c.layer_types[i]]
        q = (h @ layer["wq"].astype(ad)).reshape(
            *h.shape[:-1], c.n_heads_per_layer[i], c.head_dim)
        k = (h @ layer["wk"].astype(ad)).reshape(
            *h.shape[:-1], c.n_kv_heads, c.head_dim)
        v = (h @ layer["wv"].astype(ad)).reshape(
            *h.shape[:-1], c.n_kv_heads, c.head_dim)
        return (_rope.rotate_leading(q, cos, sin),
                _rope.rotate_leading(k, cos, sin), v)

    def _attn_out(self, layer: Params, h, out):
        """Heads' outputs `out` (..., heads, hd), gated a head by the
        sigmoid of a projection of the layer's normed input `h`, through
        W_o."""
        ad = self.config.activation_dtype
        gate = jax.nn.sigmoid(
            (h @ layer["wg"].astype(ad)).astype(jnp.float32))
        out = out * gate[..., None].astype(out.dtype)
        return out.reshape(*out.shape[:-2], -1) @ layer["wo"].astype(ad)

    def _attn_seq(self, i: int, layer: Params, h, ropes):
        """Causal attention of layer i over whole sequences h (b, s, e).
        Returns (attention output after W_o, k, v (b, s, kv heads, hd))."""
        c = self.config
        q, k, v = self._qkv(i, layer, h, ropes)
        sliding = c.layer_types[i] == SLIDING
        block_q, block_k = SLIDING_BLOCKS if sliding else FULL_BLOCKS
        qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        out = flash_attention(
            qt, kt, vt, causal=True, block_q=block_q, block_k=block_k,
            window=c.sliding_window if sliding else None)
        return self._attn_out(layer, h, out.transpose(0, 2, 1, 3)), k, v

    def _ffn(self, layer: Params, x, valid=None):
        """Feed-forward of one layer on tokens x (T, e) after the norm.
        Returns (y, expert counts or None for a dense layer)."""
        c = self.config
        ad = c.activation_dtype
        if "router" not in layer:
            gate = jax.nn.silu(x @ layer["gate"].astype(ad))
            return (gate * (x @ layer["up"].astype(ad))) @ layer[
                "down"].astype(ad), None
        y, counts = dropless_moe_ffn(
            x, layer["router"], jnp.zeros((c.num_experts,), jnp.float32),
            layer["moe_gate"], layer["moe_up"], layer["moe_down"],
            top_k=c.num_experts_per_tok, norm_topk_prob=True,
            scale=c.routed_scaling_factor, valid=valid)
        shared = jax.nn.silu(x @ layer["shared_gate"].astype(ad))
        shared = (shared * (x @ layer["shared_up"].astype(ad))) @ layer[
            "shared_down"].astype(ad)
        return y + shared, counts

    def _block_ffn(self, layer: Params, x, valid=None):
        """x (..., e) + ffn(norm(x)); returns (x, counts)."""
        h = self._norm(x, layer["mlp_norm"])
        y, counts = self._ffn(layer, h.reshape(-1, h.shape[-1]),
                              None if valid is None else valid.reshape(-1))
        return x + y.reshape(x.shape), counts

    # --------------------------------------------------------- forward
    def hidden(self, params: Params, tokens: jax.Array) -> jax.Array:
        """tokens (b, s) -> hidden states after the final norm."""
        c = self.config
        b, s = tokens.shape
        x = params["embed"].astype(c.activation_dtype)[tokens]
        ropes = self._ropes(jnp.broadcast_to(jnp.arange(s), (b, s)))
        for i, layer in enumerate(params["layers"]):
            h = self._norm(x, layer["attn_norm"])
            x = x + self._attn_seq(i, layer, h, ropes)[0]
            x, _ = self._block_ffn(layer, x)
        return self._norm(x, params["final_norm"])

    def apply(self, params: Params, tokens: jax.Array) -> jax.Array:
        """tokens (b, s) int32 -> logits (b, s, vocab) in f32."""
        x = self.hidden(params, tokens)
        head = params["lm_head"].astype(self.config.activation_dtype)
        return (x @ head).astype(jnp.float32)

    def loss(self, params: Params, batch: Dict[str, jax.Array]):
        """Causal LM loss of batch["tokens"] (b, s), as `MLAMoE.loss`.
        On a TPU the windowed flash kernel has no backward: a trainer
        differentiates this off the chip only (PERF.md section 7)."""
        tokens = batch["tokens"]
        mask = batch.get("loss_mask")
        logits = self.apply(params, tokens)[:, :-1]
        if mask is not None:
            mask = mask[:, 1:]
        loss, _ = softmax_cross_entropy(logits, tokens[:, 1:], mask=mask)
        return loss

    # ------------------------------------------------ what an engine asks
    def window_pages(self, page_size: int) -> int:
        """Pages of a sequence that its sliding layers keep: the ring a
        sequence's first table entries name (0: no such layer)."""
        c = self.config
        if not c.sliding_layers:
            return 0
        return _paged.ring_pages(c.sliding_window, page_size)

    def fixed_pages(self, page_size: int) -> int:
        """Pages of the allocator's fixed class a sequence holds for ever:
        its sliding layers' ring."""
        return self.window_pages(page_size)

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

    def prefill_counts(self, tokens: int, bucket: int) -> Dict[str, int]:
        """Nothing to add to the engine's prefill span."""
        return {}

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

    def init_cache(self, num_pages: int, page_size: int, dtype=None,
                   fixed_pages: int = 0) -> Cache:
        """`num_pages` pages in the full layers' pools, `fixed_pages` (the
        allocator's fixed class: the rings) in the sliding layers'."""
        c = self.config
        dt = dtype or c.activation_dtype
        full = (len(c.full_layers), num_pages, page_size, c.kv_dim)
        ring = (len(c.sliding_layers), max(fixed_pages, 1), page_size,
                c.kv_dim)
        make = jax.jit(lambda: {
            "k": jnp.zeros(full, dt), "v": jnp.zeros(full, dt),
            "wk": jnp.zeros(ring, dt), "wv": jnp.zeros(ring, dt),
            "moe_load": jnp.zeros((len(c.sparse_layers), c.num_experts),
                                  jnp.int32),
            "moe_step": {name: jnp.zeros((), jnp.int32)
                         for name in STEP_COUNTS}})
        return make()

    def cache_page_bytes(self, page_size: int, tp_shards: int = 1,
                         dtype=None, fixed: bool = False) -> int:
        """Bytes one page costs: keys and values of the full layers for a
        page of the pool `num_pages` counts, of the sliding layers for a
        page of the ring (`fixed`), which a fixed-class page costs
        besides."""
        c = self.config
        dt = jnp.dtype(dtype or c.activation_dtype)
        layers = len(c.sliding_layers if fixed else c.full_layers)
        return (2 * layers * page_size
                * (c.kv_dim // max(1, tp_shards)) * dt.itemsize)

    def decode_attention(self, page_size: int, dtype=None) -> str:
        """Which attention a `decode_step` traced here holds: the kernel
        of each layer kind, or "einsum"."""
        c = self.config
        if _paged.uses_kernel(c.head_dim, page_size,
                              dtype or c.activation_dtype):
            return "+".join(
                [_paged.KERNEL_PAGED_DECODE] * bool(c.full_layers)
                + [_paged.KERNEL_PAGED_WINDOW_DECODE]
                * bool(c.sliding_layers))
        return "einsum"

    def walk_block_pages(self, page_size: int, max_pages: int,
                         fixed: bool = False) -> int:
        """Pages a block of a full layer's walk holds over tables of
        `max_pages` (of a sliding layer's over its ring: `fixed`), asked
        what the kernel asks (a layer's page of keys and values)."""
        c = self.config
        layers = len(c.sliding_layers if fixed else c.full_layers)
        return _paged.walk_block_pages(
            self.cache_page_bytes(page_size, fixed=fixed) // max(1, layers),
            page_size, max_pages)

    def step_stats(self, cache: Cache) -> Dict[str, jax.Array]:
        """What the last decode step counted, still on the device, by the
        names the engine's counters take."""
        return cache["moe_step"] if self.config.sparse_layers else {}

    def cache_stats(self, cache: Cache) -> Dict[str, Any]:
        """For `EngineCore.device_stats()`: pairs an expert since the
        cache was made, by expert layer."""
        return {"moe_load": jax.device_get(cache["moe_load"]).tolist()}

    def prefill(self, params: Params, tokens: jax.Array, true_len,
                page_table: jax.Array, cache: Cache,
                page_size: int) -> Tuple[jax.Array, Cache]:
        """One padded prompt, as `models.decode.prefill`: every layer
        through the flash kernel (a sliding one with its window), keys and
        values written as whole pages in place (donate the cache): a full
        layer's all, a sliding layer's last `window_pages` into its ring.
        Padding past `true_len` is given to no expert. Returns
        (last-position logits (vocab,) f32, cache)."""
        c = self.config
        ad = c.activation_dtype
        pools = {name: cache[name] for name in ("k", "v", "wk", "wv")}
        num_pages, ring_pages = pools["k"].shape[1], pools["wk"].shape[1]
        ring = self.window_pages(page_size)
        s = tokens.shape[0]
        x = params["embed"].astype(ad)[tokens][None]            # (1, s, e)
        ropes = self._ropes(jnp.arange(s)[None])
        valid = (jnp.arange(s) < true_len)[None]
        n = -(-s // page_size)
        j = jnp.arange(n)
        held = -(-true_len // page_size)         # pages the prompt fills
        full_ids = jnp.where(j < held,
                             jnp.take(page_table, j, mode="clip"), num_pages)
        if ring:
            # the newest logical page at each ring entry, and no other
            ring_ids = jnp.where((j < held) & (j >= held - ring),
                                 jnp.take(page_table, j % ring, mode="clip"),
                                 ring_pages)
        order = {FULL: ("k", "v", full_ids, c.full_layers),
                 SLIDING: ("wk", "wv", ring_ids if ring else None,
                           c.sliding_layers)}

        def pages(a):
            a = jnp.pad(a[0].reshape(s, c.kv_dim),
                        ((0, n * page_size - s), (0, 0)))
            return a.reshape(n, page_size, c.kv_dim)

        for i, layer in enumerate(params["layers"]):
            h = self._norm(x, layer["attn_norm"])
            attn, k, v = self._attn_seq(i, layer, h, ropes)
            kn, vn, ids, layers = order[c.layer_types[i]]
            li = layers.index(i)
            pools[kn] = pools[kn].at[li, ids].set(
                pages(k).astype(pools[kn].dtype), mode="drop")
            pools[vn] = pools[vn].at[li, ids].set(
                pages(v).astype(pools[vn].dtype), mode="drop")
            x = x + attn
            x, _ = self._block_ffn(layer, x, valid)
        x = self._norm(x, params["final_norm"])
        last = jnp.take(x[0], true_len - 1, axis=0)
        logits = (last @ params["lm_head"].astype(ad)).astype(jnp.float32)
        return logits, {**cache, **pools}

    def decode_step(self, params: Params, cache: Cache, tokens: jax.Array,
                    positions: jax.Array, page_tables: jax.Array,
                    active: jax.Array,
                    page_size: int) -> Tuple[jax.Array, Cache]:
        """Advance a padded batch by one token each, as
        `models.decode.decode_step`. Inactive lanes write nothing and are
        given to no expert. Returns (logits (B, vocab) f32, cache) —
        donate the cache."""
        c = self.config
        ad = c.activation_dtype
        pools = {name: cache[name] for name in ("k", "v", "wk", "wv")}
        num_pages, ring_pages = pools["k"].shape[1], pools["wk"].shape[1]
        ring = self.window_pages(page_size)
        B = tokens.shape[0]
        x = params["embed"].astype(ad)[tokens]                  # (B, e)
        ropes = self._ropes(positions)                # (B, 1, rot / 2)
        lengths = jnp.where(active, positions + 1, 0)
        logical = positions // page_size
        slot = positions % page_size

        def write_page(entry, tables, oob):
            page = jnp.take_along_axis(tables, entry[:, None], axis=1)[:, 0]
            return jnp.where(active & (page >= 0), page, oob)

        full_page = write_page(logical, page_tables, num_pages)
        if ring:
            ring_tables = page_tables[:, :ring]
            ring_page = write_page(logical % ring, ring_tables, ring_pages)
        load = cache["moe_load"]
        pairs = touched = load_max = jnp.int32(0)
        for i, layer in enumerate(params["layers"]):
            h = self._norm(x, layer["attn_norm"])
            q, k, v = self._qkv(i, layer, h, ropes)
            k, v = k.reshape(B, c.kv_dim), v.reshape(B, c.kv_dim)
            if c.layer_types[i] == FULL:
                li = c.full_layers.index(i)
                pools["k"] = pools["k"].at[li, full_page, slot].set(
                    k.astype(pools["k"].dtype), mode="drop")
                pools["v"] = pools["v"].at[li, full_page, slot].set(
                    v.astype(pools["v"].dtype), mode="drop")
                out = _paged.paged_decode_attention(
                    q.astype(pools["k"].dtype), pools["k"], pools["v"], li,
                    page_tables, lengths)
            else:
                li = c.sliding_layers.index(i)
                pools["wk"] = pools["wk"].at[li, ring_page, slot].set(
                    k.astype(pools["wk"].dtype), mode="drop")
                pools["wv"] = pools["wv"].at[li, ring_page, slot].set(
                    v.astype(pools["wv"].dtype), mode="drop")
                out = _paged.paged_window_decode_attention(
                    q.astype(pools["wk"].dtype), pools["wk"], pools["wv"],
                    li, ring_tables, lengths, c.sliding_window)
            x = x + self._attn_out(layer, h, out.astype(ad))
            x, counts = self._block_ffn(layer, x, active)
            if counts is not None:
                load = load.at[c.sparse_layers.index(i)].add(counts["load"])
                pairs = pairs + counts["pairs"]
                touched = touched + counts["touched"]
                load_max = load_max + jnp.max(counts["load"])
        x = self._norm(x, params["final_norm"])
        logits = (x @ params["lm_head"].astype(ad)).astype(jnp.float32)
        return logits, {**pools, "moe_load": load,
                        "moe_step": dict(zip(STEP_COUNTS, (
                            pairs, touched, load_max)))}
