"""Incremental decoding for the ray_tpu Transformer: paged KV cache.

The serving engine reaches this module through `Transformer`'s own
`init_cache` / `prefill` / `decode_step` / `cache_page_bytes` /
`decode_attention`: it asks the model its config names and imports
nothing here. `models.mla_moe.MLAMoE` answers the same questions with a
latent pool (one row `[c_kv | k_rope]` a position and layer, every head's)
and a decode step through experts; this file is the dense decoder's, keys
and values per kv head.

Serving needs two forwards the training graph never runs: a *prefill*
that processes a whole prompt once while writing every layer's K/V
into cache pages, and a *decode step* that advances a batch of
sequences by one token each against their cached context. Both mirror
`Transformer._layer` exactly (rms_norm / GQA / RoPE / SwiGLU on the
same ops) so prefill+decode logits agree with `Transformer.apply` to
float tolerance — tests pin that equivalence.

The cache is paged (vLLM-style): per layer, `(num_pages, page_size,
kv_heads * head_dim)` arrays, and a sequence owns an arbitrary set of
pages listed in its page table. Paging is what makes continuous
batching viable — a finished sequence returns its pages to the pool
immediately instead of stranding a max-length slab.

Layout note: pages are stacked on a leading layers axis, matching the
stacked/scanned parameter layout, and a position is one row of all its
kv heads side by side: a page is contiguous, `(page_size, kv * hd)`
tiles as the paged decode kernel (`ops.paged_attention`) copies it in,
and the stacked pool is what the kernel takes, with the layer's index,
so no per-layer slice of it is ever made. Prefill scans the layer body
(one compile regardless of depth) and writes whole pages; the decode
step unrolls a Python loop over layers — at serving depths that compile
cost is paid once per (batch, pages) shape.

The decode step's q / k / v products stay flat `(B, 1, heads * hd)`
through one `lax.optimization_barrier`: with the view into heads in the
compiler's reach it folds it into the dot and, for that, writes every
layer's wq and wk out transposed in every step (a quarter of the step's
bytes). `tests/test_kernel_names_aot.py` pins that the step compiled
for a v5e makes no copy of either. `_qkv` is `prefill`'s alone.

Both programs move cache bytes in proportion to what the lanes hold,
not to the pool or the context limit. The writes are scatters into the
pool itself, which holds only if the caller donates the cache
(`EngineCore` jits both with `donate_argnums`): without donation XLA
copies both pools whole before the first scatter. The decode step's
attention reads through `ops.paged_attention.paged_decode_attention`:
the kernel where the platform is a TPU and the shapes tile, the gather
and masked einsum over the whole table elsewhere (`decode_attention`
says which).

Out-of-range page writes use `num_pages` as the drop sentinel: scatter
mode="drop" discards them, which is how pages past a prompt and
inactive decode rows stay out of the cache without branching. A
prefill writes its last page whole: the rows past the prompt hold the
padding's keys until the decode steps that reach them overwrite them,
and nothing reads a position before it is written.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import regions as R
from ray_tpu.models.gqa import FULL_BLOCKS
from ray_tpu.models.paged import decode_lanes
from ray_tpu.ops import paged_attention as _paged
from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.rope import apply_rope_cached, rope_cos_sin

Params = Dict[str, Any]
KVCache = Dict[str, jax.Array]


def cache_sharding(config, mesh):
    """How the paged cache lies on a mesh: kv heads (blocks of a row's
    lanes) over `tp`, the rest whole — each tp shard holds its own
    heads' pages, which is what `cache_page_bytes(tp_shards=...)`
    charges for."""
    tp = mesh.shape.get("tp", 1)
    if config.kv_heads % tp:
        raise ValueError(
            f"the KV cache shards its {config.kv_heads} kv heads over "
            f"the mesh's tp axis of {tp}, which does not divide them; "
            f"use a tp that divides kv_heads")
    return NamedSharding(mesh, P(None, None, None,
                                 "tp" if tp > 1 else None))


def init_paged_cache(config, num_pages: int, page_size: int,
                     dtype=None, mesh=None) -> KVCache:
    """Zeroed paged cache: k/v each (layers, pages, page, kv * hd),
    created sharded as `cache_sharding` says when given a mesh."""
    if config.moe_num_experts:
        raise NotImplementedError(
            "the capacity-factor MoE of Transformer (models.moe.moe_ffn) "
            "drops tokens and is not served; the serving path through "
            "experts is the dropless layer of models.mla_moe.MLAMoE "
            "(models.moe.dropless_moe_ffn)")
    dt = dtype or config.activation_dtype
    shape = (config.n_layers, num_pages, page_size,
             config.kv_heads * config.head_dim)
    sharding = cache_sharding(config, mesh) if mesh is not None else None
    zeros = jax.jit(lambda: jnp.zeros(shape, dt), out_shardings=sharding)
    return {"k": zeros(), "v": zeros()}


def cache_page_bytes(config, page_size: int, tp_shards: int = 1,
                     dtype=None) -> int:
    """Bytes one page costs per shard (k+v, all layers). The engine
    sizes its pool off this: kv heads split across tp shards, so a
    bigger mesh affords more pages for the same per-chip budget."""
    dt = jnp.dtype(dtype or config.activation_dtype)
    kv_local = max(1, config.kv_heads // max(1, tp_shards))
    return (2 * config.n_layers * page_size * kv_local
            * config.head_dim * dt.itemsize)


def walk_block_pages(config, page_size: int, max_pages: int,
                     tp_shards: int = 1, dtype=None) -> int:
    """Pages a block of the decode kernel's walk holds over tables of
    `max_pages`: `ops.paged_attention.walk_block_pages` asked what the
    kernel asks it, a layer's page of keys and values on one shard."""
    return _paged.walk_block_pages(
        cache_page_bytes(config, page_size, tp_shards, dtype)
        // config.n_layers, page_size, max_pages)


def decode_attention(config, page_size: int, dtype=None) -> str:
    """Which attention a `decode_step` traced here holds: the kernel's
    name, or "einsum" (the platform being traced for and the shapes
    decide, `ops.paged_attention.uses_kernel`)."""
    dt = dtype or config.activation_dtype
    if _paged.uses_kernel(config.head_dim, page_size, dt):
        return _paged.KERNEL_PAGED_DECODE
    return "einsum"


@R.region(R.ATTN_IN)
def _qkv(config, layer: Params, h):
    ad = config.activation_dtype
    b, s, _ = h.shape
    hd = config.head_dim
    q = (h @ layer["wq"].astype(ad)).reshape(b, s, config.n_heads, hd)
    k = (h @ layer["wk"].astype(ad)).reshape(b, s, config.kv_heads, hd)
    v = (h @ layer["wv"].astype(ad)).reshape(b, s, config.kv_heads, hd)
    return q, k, v


def _mlp(model, layer: Params, x):
    config = model.config
    ad = config.activation_dtype
    h = model._norm(x, layer["mlp_norm"])
    with R.region(R.FFN):
        gate = jax.nn.silu(h @ layer["gate"].astype(ad))
        up = h @ layer["up"].astype(ad)
        return x + (gate * up) @ layer["down"].astype(ad)


def prefill(model, params: Params, tokens: jax.Array, true_len,
            page_table: jax.Array, cache: KVCache,
            page_size: int) -> Tuple[jax.Array, KVCache]:
    """Process one padded prompt, writing K/V into the cache pages.

    tokens: (s_pad,) int32, garbage past true_len (the causal mask
    keeps the tail from contaminating positions < true_len).
    true_len: scalar int32, actual prompt length.
    page_table: (max_pages,) int32 page ids; entries past the prompt's
    pages may be anything (writes there are dropped).
    cache: donate it, or both pools are copied whole.

    Returns (last-position logits (vocab,) f32, updated cache).
    """
    c = model.config
    ad = c.activation_dtype
    num_pages = cache["k"].shape[1]
    s = tokens.shape[0]
    toks = tokens[None]                                   # (1, s)
    positions = jnp.arange(s)[None]
    with R.region(R.EMBED):
        x = model._embed_lookup(params["embed"].astype(ad), toks)
    with R.region(R.ATTN_IN):
        rope = rope_cos_sin(positions, c.head_dim, c.rope_theta)
    cos, sin = rope

    def body(x, layer):
        h = model._norm(x, layer["attn_norm"])
        q, k, v = _qkv(c, layer, h)
        with R.region(R.ATTN_IN):
            q = apply_rope_cached(q, cos, sin)
            k = apply_rope_cached(k, cos, sin)
            qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        with R.region(R.ATTN_CORE):
            # the blocks follow the bucket, as the other served classes'
            # do (the call cuts a block to `s`: a bucket under 1,024 is one
            # block a head); `attn_block_q` / `attn_block_k` are training's
            block_q, block_k = FULL_BLOCKS
            attn = flash_attention(qt, kt, vt, causal=True,
                                   block_q=block_q, block_k=block_k,
                                   mesh=model.kernel_mesh)
        with R.region(R.ATTN_OUT):
            attn = attn.transpose(0, 2, 1, 3).reshape(
                1, s, c.n_heads * c.head_dim)
            x = x + attn @ layer["wo"].astype(ad)
        x = _mlp(model, layer, x)
        return x, (k[0], v[0])                     # (s, kv, hd) each

    x, (ks, vs) = lax.scan(body, x, params["layers"])
    x = model._final_norm(params, x)
    with R.region(R.HEAD):
        last = jnp.take(x[0], true_len - 1, axis=0)
        logits = (last @ model._head(params).astype(ad)).astype(
            jnp.float32)

    # whole pages: (layers, s, kv, hd) -> (layers, pages, page, kv * hd)
    n = -(-s // page_size)
    with R.region(R.CACHE):
        first = jnp.arange(n) * page_size
        page_ids = jnp.take(page_table, jnp.arange(n), mode="clip")
        # pages past the prompt scatter to the drop sentinel
        page_ids = jnp.where(first < true_len, page_ids, num_pages)

        layer_ids = jnp.arange(c.n_layers)[:, None]

    @R.region(R.ATTN_IN)    # the cache write, every layer's at once
    def paged(a, pool):
        a = a.astype(pool.dtype).reshape(c.n_layers, s, -1)
        a = jnp.pad(a, ((0, 0), (0, n * page_size - s), (0, 0)))
        # both indices explicit: a slice over the layers would make the
        # CPU backend transpose the pool to scatter and copy it back
        return pool.at[layer_ids, page_ids[None, :]].set(
            a.reshape(c.n_layers, n, page_size, -1), mode="drop")

    return logits, {"k": paged(ks, cache["k"]), "v": paged(vs, cache["v"])}


def decode_step(model, params: Params, cache: KVCache,
                tokens: jax.Array, positions: jax.Array,
                page_tables: jax.Array, active: jax.Array,
                page_size: int) -> Tuple[jax.Array, KVCache]:
    """Advance a padded batch by one token each.

    tokens: (B,) int32 current input token per row.
    positions: (B,) int32 absolute position the token occupies.
    page_tables: (B, max_pages) int32, -1 for unassigned slots.
    active: (B,) bool — inactive (padding) rows neither write cache
    nor produce meaningful logits.
    cache: donate it, or both pools are copied whole.

    Returns (logits (B, vocab) f32, updated cache).
    """
    c = model.config
    ad = c.activation_dtype
    hd = c.head_dim
    ck, cv = cache["k"], cache["v"]
    num_pages = ck.shape[1]
    B = tokens.shape[0]

    with R.region(R.EMBED):
        x = model._embed_lookup(params["embed"].astype(ad),
                                tokens[:, None])           # (B, 1, e)
    with R.region(R.ATTN_IN):
        cos, sin = rope_cos_sin(positions[:, None], hd, c.rope_theta)

    # cache slot j is visible iff j <= position and its page is assigned
    # (own-position k/v is written before the read)
    wr_page, wr_slot, lengths = decode_lanes(positions, page_tables, active,
                                             num_pages, page_size)

    layers = params["layers"]
    for i in range(c.n_layers):
        layer = jax.tree_util.tree_map(lambda a: a[i], layers)
        h = model._norm(x, layer["attn_norm"])
        with R.region(R.ATTN_IN):
            # flat until past the barrier (the module's docstring says
            # why)
            q, k, v = lax.optimization_barrier(tuple(
                h @ layer[w].astype(ad) for w in ("wq", "wk", "wv")))
            q = apply_rope_cached(q.reshape(B, 1, c.n_heads, hd), cos, sin)
            k = apply_rope_cached(k.reshape(B, 1, c.kv_heads, hd), cos,
                                  sin)
            ck = ck.at[i, wr_page, wr_slot].set(
                k.astype(ck.dtype).reshape(B, -1), mode="drop")
            cv = cv.at[i, wr_page, wr_slot].set(
                v[:, 0].astype(cv.dtype), mode="drop")
        with R.region(R.ATTN_CORE):
            out = _paged.paged_decode_attention(
                q[:, 0], ck, cv, i, page_tables, lengths,
                mesh=model.kernel_mesh)
        with R.region(R.ATTN_OUT):
            out = out.astype(ad).reshape(B, 1, c.n_heads * hd)
            x = x + out @ layer["wo"].astype(ad)
        x = _mlp(model, layer, x)

    x = model._final_norm(params, x)
    with R.region(R.HEAD):
        logits = (x[:, 0] @ model._head(params).astype(ad))
        return logits.astype(jnp.float32), {"k": ck, "v": cv}
