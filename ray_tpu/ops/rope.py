"""Rotary position embeddings (RoPE).

Pure JAX: RoPE is elementwise sin/cos mul-add and XLA fuses it into the
surrounding QK projections; a hand kernel buys nothing here. Supports an
absolute `positions` argument so sequence-parallel shards (each holding a
seq slice) rotate with their *global* positions — required for ring
attention (ray_tpu/ops/ring_attention.py).

A model may rotate only the leading part of a head (`rotate_leading`:
`partial_rotary_factor`) and may scale its frequencies for contexts longer
than it was trained on (`yarn_frequencies`, with cos and sin multiplied by
an attention factor: `cos_sin`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    """Inverse frequencies for each rotated pair, shape (head_dim//2,)."""
    if head_dim % 2:
        raise ValueError(f"head_dim must be even, got {head_dim}")
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)


def rope_cos_sin(positions: jax.Array, head_dim: int,
                 theta: float = 10000.0):
    """Precompute (cos, sin), each (..., seq, 1, head_dim//2) f32.

    Compute once per forward pass and reuse across layers/remat passes —
    the transcendentals are VPU-expensive and identical for every layer.
    """
    inv_freq = rope_frequencies(head_dim, theta)
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    angles = angles[..., None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope_cached(x: jax.Array, cos: jax.Array,
                      sin: jax.Array) -> jax.Array:
    """Rotate x (..., seq, heads, head_dim) by precomputed cos/sin."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0) -> jax.Array:
    """Rotate x of shape (..., seq, heads, head_dim) by per-token angles.

    positions: integer array broadcastable to x.shape[:-2] + (seq,) —
    usually (batch, seq) or (seq,). Split-halves convention (llama):
    the first half of head_dim pairs with the second half.
    """
    cos, sin = rope_cos_sin(positions, x.shape[-1], theta)
    return apply_rope_cached(x, cos, sin)


def yarn_frequencies(rotary_dim: int, theta: float, factor: float,
                     original_max_position: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0) -> jax.Array:
    """YaRN inverse frequencies, shape (rotary_dim//2,): pair i turns at
    its own frequency where it makes more than `beta_fast` rotations over
    the original context, at 1/`factor` of it where it makes fewer than
    `beta_slow`, and at a blend in between, by a linear ramp over the pairs
    between the two correction dimensions (rounded outwards)."""
    plain = rope_frequencies(rotary_dim, theta)

    def correction_dim(rotations: float) -> float:
        return (rotary_dim * math.log(original_max_position
                                      / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001                   # no division by zero
    ramp = jnp.clip((jnp.arange(rotary_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def cos_sin(positions: jax.Array, inv_freq: jax.Array, scale: float = 1.0):
    """(cos, sin) of `positions` at given inverse frequencies, both times
    `scale` (YaRN's attention factor), each (..., seq, 1, len(inv_freq))
    f32, as `rope_cos_sin` lays them."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    angles = angles[..., None, :]
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def rotate_leading(x: jax.Array, cos: jax.Array, sin: jax.Array
                   ) -> jax.Array:
    """Rotate the leading `2 * cos.shape[-1]` numbers of each head of x
    (..., seq, heads, head_dim), split halves inside that part, and pass
    the rest through (`partial_rotary_factor` under 1)."""
    rot = 2 * cos.shape[-1]
    if rot == x.shape[-1]:
        return apply_rope_cached(x, cos, sin)
    return jnp.concatenate(
        [apply_rope_cached(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)
