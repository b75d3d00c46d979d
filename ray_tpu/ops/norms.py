"""RMSNorm / LayerNorm with fused Pallas forward.

Memory-bound ops: the win over XLA's default lowering is avoiding the
extra HBM round-trip between the moment computation and the scale apply.
Backward is left to XLA via a reference-recompute custom_vjp — the
recompute is VMEM-resident and fuses into the surrounding backward.

The forward is always the kernel: compiled when the target platform is
a TPU, interpreted elsewhere. Given the `mesh` the activation is
sharded over it runs inside `jax.shard_map`, because GSPMD cannot
partition a Mosaic kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.dispatch import activation_spec, on_tpu, shard_kernel

_BLOCK_ROWS = 256
# the most numbers a block holds: 256 rows up to 4,096 wide; a wider row
# gets fewer (160 at 6,144, where 256 rows pass the kernel's 16 MB of VMEM)
_BLOCK_NUMBERS = _BLOCK_ROWS * 4096
# its name in a device trace (see ops/attention.py, KERNEL_FWD)
KERNEL_RMS_FWD = "rms_norm_fwd"


# ---------------------------------------------------------------- rmsnorm
def rms_norm_reference(x: jax.Array, w: jax.Array,
                       eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dtype)


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * (1.0 + w_ref[:].astype(jnp.float32))).astype(o_ref.dtype)


def _rms_fwd_pallas(x2d: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    rows, d = x2d.shape
    # Rows are independent, so a ragged last block is harmless: what it
    # reads past the end it also writes past the end, and that is
    # dropped.
    block_rows = min(rows, _BLOCK_ROWS,
                     max(16, _BLOCK_NUMBERS // d // 16 * 16))
    grid = (pl.cdiv(rows, block_rows),)
    call = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct((rows, d), x2d.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        interpret=not on_tpu(),
        name=KERNEL_RMS_FWD,
    )
    return call(x2d, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6,
             mesh=None) -> jax.Array:
    """y = x * rsqrt(mean(x^2) + eps) * (1 + w), fused.

    Follows the (1 + w) convention (gemma/llama3 style) so a zero-init
    scale is the identity. Accepts any leading shape; normalises the
    last axis. `mesh`: the mesh of more than one device a (batch, seq,
    embed) activation is sharded over (`ops.dispatch.kernel_mesh`), or
    None.
    """
    if mesh is not None:
        spec = activation_spec(mesh)
        return shard_kernel(lambda x_, w_: rms_norm(x_, w_, eps), mesh,
                            (spec, P()), spec)(x, w)
    d = x.shape[-1]
    return _rms_fwd_pallas(x.reshape(-1, d), w, eps).reshape(x.shape)


def _rms_fwd_rule(x, w, eps, mesh):
    return rms_norm(x, w, eps, mesh), (x, w)


def _rms_bwd_rule(eps, mesh, res, g):
    x, w = res
    _, vjp = jax.vjp(lambda x_, w_: rms_norm_reference(x_, w_, eps), x, w)
    return vjp(g)


rms_norm.defvjp(_rms_fwd_rule, _rms_bwd_rule)


# -------------------------------------------------------------- layernorm
def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dtype)
