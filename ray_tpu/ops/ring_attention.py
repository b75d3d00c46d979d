"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

Net-new capability relative to the reference, which has no sequence/context
parallelism in-tree (SURVEY.md §2.4, §5.7). Each device holds a sequence
shard of Q/K/V; K/V chunks rotate around the `sp` ring via
`lax.ppermute` while every device accumulates its Q shard's attention
with online-softmax merging — O(seq/n) memory per device, compute
overlapped with ICI transfer by XLA's latency-hiding scheduler.

Call :func:`ring_attention` inside `shard_map` (it uses collective axis
ops), or :func:`ring_attention_sharded` for a jit-level entry point that
wraps the shard_map with standard (batch, heads, seq) specs.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from ray_tpu.ops.attention import DEFAULT_MASK_VALUE, flash_attention
from ray_tpu.ops.dispatch import attention_specs, kernel_mesh

NEG_INF = -jnp.inf


def _chunk_attention(q, k, v, q_off, k_off, causal, sm_scale):
    """Attention of a Q shard against one K/V chunk; returns (o, lse) f32.

    GQA-aware: q has (b, h, sq, d) with h = g * kvh; k/v stay at their
    raw kv-head count and are matched via a grouped einsum, so the ring
    never transfers or stores repeated K/V. Offsets are *global* token
    positions of the shard starts, so the causal mask is exact across
    ring steps. Fully-masked rows yield lse = -inf and a zero output,
    which the merge treats as "no mass".
    """
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    g = h // kvh
    qf = q.astype(jnp.float32).reshape(b, kvh, g, sq, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bngqd,bnkd->bngqk", qf, kf,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        qi = q_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        ki = k_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(qi >= ki, s, DEFAULT_MASK_VALUE)
    m = jnp.max(s, axis=-1)                                 # (b,n,g,sq)
    # Rows with every entry masked: treat as zero mass.
    dead = m <= DEFAULT_MASK_VALUE / 2
    m_safe = jnp.where(dead, 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(dead[..., None], 0.0, p)
    l = jnp.sum(p, axis=-1)
    # Normalised partial output: _merge expects each partial to be a
    # proper softmax-weighted average with its mass carried in lse.
    o = jnp.einsum("bngqk,bnkd->bngqd", p, vf) / jnp.maximum(
        l, 1e-37)[..., None]
    lse = jnp.where(dead | (l == 0.0), NEG_INF, m_safe + jnp.log(
        jnp.maximum(l, 1e-37)))
    return o.reshape(b, h, sq, d), lse.reshape(b, h, sq)


def _merge(o1, lse1, o2, lse2):
    """Combine two partial attention results via their log-sum-exps."""
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(jnp.isinf(m) & (m < 0), 0.0, m)
    w1 = jnp.where(jnp.isinf(lse1) & (lse1 < 0), 0.0,
                   jnp.exp(lse1 - m_safe))
    w2 = jnp.where(jnp.isinf(lse2) & (lse2 < 0), 0.0,
                   jnp.exp(lse2 - m_safe))
    tot = w1 + w2
    safe_tot = jnp.maximum(tot, 1e-37)
    o = (o1 * w1[..., None] + o2 * w2[..., None]) / safe_tot[..., None]
    o = jnp.where(tot[..., None] == 0.0, 0.0, o)
    lse = jnp.where(tot == 0.0, NEG_INF, m_safe + jnp.log(safe_tot))
    return o, lse


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis: str = "sp", causal: bool = True,
                   sm_scale: Optional[float] = None) -> jax.Array:
    """Exact attention over seq shards; call inside shard_map.

    q (b, h, s_local, d); k/v (b, kvh, s_local, d). The number of ring
    steps is the static mesh axis size, so the loop unrolls at trace
    time and XLA overlaps each step's ppermute with the previous step's
    compute.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    h, kvh = q.shape[1], k.shape[1]
    if h % kvh:
        raise ValueError(
            f"num_heads ({h}) must be a multiple of num_kv_heads ({kvh})")
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    s_local = q.shape[2]
    q_off = idx * s_local

    perm = [(i, (i + 1) % n) for i in range(n)]
    o = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
    lse = jnp.full(q.shape[:3], NEG_INF, jnp.float32)
    kr, vr = k, v
    # Remat each chunk so backward recomputes the (s_local, s_local)
    # scores instead of saving them per ring step — keeps the O(seq/n)
    # memory claim true under jax.grad.
    chunk = jax.checkpoint(_chunk_attention, static_argnums=(5, 6))
    for r in range(n):
        # chunk currently held arrived from device (idx - r) mod n
        k_off = ((idx - r) % n) * s_local
        o_r, lse_r = chunk(q, kr, vr, q_off, k_off, causal, sm_scale)
        o, lse = _merge(o, lse, o_r, lse_r)
        if r != n - 1:
            kr = lax.ppermute(kr, axis, perm)
            vr = lax.ppermute(vr, axis, perm)
    return o.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: Mesh, causal: bool = True,
                           sm_scale: Optional[float] = None,
                           axis: str = "sp") -> jax.Array:
    """jit-level wrapper: shards seq over `axis`, batch over the data
    axes present in the mesh (dp/fsdp), heads over tp when present, and
    runs the ring. Falls back to flash/reference attention when the
    sequence axis is trivial.

    Works on any user-built Mesh: specs are assembled from the axes the
    mesh actually has, so a mesh lacking dp/fsdp/tp (e.g. a bare
    ``Mesh(devs, ("sp",))``) shards only the sequence axis.
    """
    if mesh.shape.get(axis, 1) == 1:
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               mesh=kernel_mesh(mesh))
    spec_q, spec_kv, repeat_kv = attention_specs(
        mesh, q.shape[1], k.shape[1], seq_axis=axis)
    if repeat_kv:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    fn = jax.shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis=axis,
                                          causal=causal, sm_scale=sm_scale),
        mesh=mesh, in_specs=(spec_q, spec_kv, spec_kv), out_specs=spec_q,
        check_vma=False)
    return fn(q, k, v)
