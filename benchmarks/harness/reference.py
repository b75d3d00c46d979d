"""The arithmetic every plain reference shares: straightforward float32
`jax.numpy` at matmul precision `highest`, no kernels. The reference of one
architecture (its layers, `logits_fn`, `loss_fn`, `reference_rows`) is in
that architecture's file under `benchmarks/models/`; nothing here or there
comes from `ray_tpu/`.

`quant` puts the control in the reference's place: every matmul operand
(weights, activations, keys and values) is rounded to the precision below
the configuration's (fp8 e4m3 with a per-tensor scale) before use.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _ident(x):
    return x


@jax.custom_vjp
def fp8_round(x):
    """x rounded to fp8 e4m3 under a per-tensor scale; the gradient passes
    straight through, as a quantised training path would let it."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


fp8_round.defvjp(lambda x: (fp8_round(x), None), lambda _, g: (g,))


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision=HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, positions, theta):
    """x (s, heads, hd); split-halves pairing (i with i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions.astype(F32)[:, None, None] * inv
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def rel_rms(a, b) -> float:
    """||a - b|| / ||b||, b the reference."""
    a = jnp.asarray(a, F32)
    b = jnp.asarray(b, F32)
    return float(jnp.sqrt(jnp.sum(jnp.square(a - b))
                          / jnp.sum(jnp.square(b))))
