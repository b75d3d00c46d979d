"""Target-platform resolution for Pallas kernel dispatch.

Pallas TPU kernels must run in interpret mode on CPU, and the decision
has to follow the devices the computation will actually run on — not
`jax.default_backend()`. On a TPU host that builds a virtual CPU mesh
(the multi-chip dry run, tests), the default backend says "tpu" while
the mesh says "cpu"; keying off the default backend then lowers a
compiled TPU kernel onto CPU, which XLA rejects.

Ops call `on_tpu()`; code that knows its target devices (a model bound
to a mesh, a trainer) wraps tracing in `compute_platform(...)`. The
override is a contextvar read at *trace* time, so it composes with jit:
whatever platform is active while the function is being traced wins.

A Mosaic kernel cannot be partitioned by GSPMD, so on a mesh of more
than one device every kernel call runs inside `jax.shard_map`;
`kernel_mesh` and `attention_specs` say over which mesh and how.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, Optional

import jax
from jax.sharding import PartitionSpec as P

_PLATFORM_OVERRIDE: contextvars.ContextVar[Optional[str]] = (
    contextvars.ContextVar("ray_tpu_compute_platform", default=None))


def mesh_platform(mesh) -> str:
    """Platform string ("tpu"/"cpu"/...) of a Mesh's devices."""
    return mesh.devices.flat[0].platform


@contextlib.contextmanager
def compute_platform(platform: Optional[str]) -> Iterator[None]:
    """Pin the platform ops should compile for while tracing under this
    context. `None` is a no-op (defer to the default backend)."""
    if platform is None:
        yield
        return
    token = _PLATFORM_OVERRIDE.set(platform)
    try:
        yield
    finally:
        _PLATFORM_OVERRIDE.reset(token)


def platform_pinned() -> bool:
    """True inside a `compute_platform(...)` block: the caller has said
    what it is compiling for (an ahead-of-time lowering for another
    platform), and a model must not overrule it from its mesh."""
    return _PLATFORM_OVERRIDE.get() is not None


def target_platform() -> str:
    override = _PLATFORM_OVERRIDE.get()
    if override is not None:
        return override
    return jax.default_backend()


def on_tpu() -> bool:
    return target_platform() == "tpu"


def kernel_mesh(mesh):
    """The mesh a kernel call must be shard-mapped over, or None when
    there is nothing to partition (no mesh, or a single device)."""
    return mesh if mesh is not None and mesh.size > 1 else None


def shard_kernel(fn, mesh, in_specs, out_specs):
    """`jax.shard_map` of a kernel call over `mesh`. Inside another
    shard_map (a pipeline stage is manual over `pp`) it must use the
    context mesh and take only the axes that are not manual yet."""
    ctx = jax.sharding.get_abstract_mesh()
    outer = set(ctx.manual_axes) if not ctx.empty else set()
    if not outer:
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
    return jax.shard_map(fn, axis_names=set(mesh.axis_names) - outer,
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def _nontrivial(mesh, *axes) -> tuple:
    return tuple(a for a in axes if mesh.shape.get(a, 1) > 1)


def activation_spec(mesh, seq_axis: Optional[str] = "sp") -> P:
    """Spec of a (batch, seq, embed) activation inside shard_map: batch
    over the data axes the mesh has, seq over `seq_axis`, embed whole."""
    seq = _nontrivial(mesh, seq_axis) if seq_axis else ()
    return P(_nontrivial(mesh, "dp", "fsdp") or None,
             seq[0] if seq else None, None)


def attention_specs(mesh, heads: int, kv_heads: int,
                    seq_axis: Optional[str] = None):
    """Specs of (batch, heads, seq, head_dim) attention operands inside
    shard_map: batch over the data axes, heads over `tp`, seq over
    `seq_axis` (ring attention) or whole. Only axes the mesh has and
    that are nontrivial are named — a spec naming an absent axis raises
    inside shard_map.

    Returns (spec_q, spec_kv, repeat_kv). kv heads shard over tp beside
    the q heads when they divide; a single kv head (MQA) replicates;
    any other GQA shape has `repeat_kv` set: the caller repeats K/V to
    `heads` first, because a tp device holds a contiguous block of q
    heads and the kernels' local q-to-kv grouping would misalign. That
    costs heads/kv_heads in K/V memory — prefer kv_heads % tp == 0."""
    batch = tuple(a for a in _nontrivial(mesh, "dp", "fsdp")
                  if a != seq_axis) or None
    head = "tp" if seq_axis != "tp" and _nontrivial(mesh, "tp") else None
    seq = seq_axis if seq_axis and _nontrivial(mesh, seq_axis) else None
    spec_q = P(batch, head, seq, None)
    tp = mesh.shape[head] if head else 1
    if kv_heads % tp == 0:
        return spec_q, spec_q, False
    if kv_heads == 1:
        return spec_q, P(batch, None, seq, None), False
    return spec_q, spec_q, True
