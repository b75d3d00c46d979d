"""GPipe pipeline parallelism over the `pp` mesh axis.

The reference builds pipeline schedules out of compiled actor DAGs with
NCCL p2p channels (reference python/ray/dag/dag_node_operation.py,
experimental/channel/torch_tensor_nccl_channel.py). The TPU-native
equivalent is a SPMD microbatch schedule INSIDE one XLA program:
`jax.shard_map` manual over ONLY the pp axis (other mesh axes — dp,
fsdp, tp, sp — stay auto, so pipeline composes with GSPMD sharding),
with `lax.ppermute` rotating activations stage→stage over ICI/DCN.

Schedule: classic GPipe fill-drain. With S stages and M microbatches
the loop runs M+S-1 ticks; stage 0 injects microbatch t at tick t, the
last stage emits microbatch t-(S-1). Bubble fraction (S-1)/(M+S-1)
shrinks as M grows — choose M ≥ 4·S for <20% bubble (config knob
`pipeline_microbatches`).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def partition_layers(n_layers: int, n_stages: int) -> list:
    """Canonical stage partition: ``[(start, count), ...]`` per stage,
    with the remainder layers assigned to the LAST stage (it already
    sits next to the loss, so in MPMD mode its extra work overlaps the
    other stages' cooldown bubble). Shared by the SPMD schedules here
    (uneven splits via ``layer_fn``) and by the MPMD stage assignment
    (train/pipeline.py), so the two parallelism modes can never
    disagree about which stage owns which layer."""
    if n_stages < 1:
        raise ValueError(f"need at least one stage, got {n_stages}")
    if n_layers < n_stages:
        raise ValueError(
            f"{n_layers} layers cannot fill {n_stages} pipeline stages")
    k, r = divmod(n_layers, n_stages)
    parts = [(s * k, k) for s in range(n_stages - 1)]
    parts.append(((n_stages - 1) * k, k + r))
    return parts


def slice_stage(layer_params: Any, start: int, count: int) -> Any:
    """One stage's sub-stack: leaves (L, ...) -> (count, ...). The MPMD
    counterpart of split_stages — per-stage pytrees may be RAGGED
    across stages (each stage is its own program), which is exactly why
    uneven splits are free in MPMD mode."""
    return jax.tree_util.tree_map(
        lambda p: p[start:start + count], layer_params)


def split_stages(layer_params: Any, n_stages: int) -> Any:
    """Reshape layer-stacked leaves (L, ...) -> (S, L//S, ...)."""
    def reshape(p):
        L = p.shape[0]
        if L % n_stages:
            raise ValueError(
                f"{L} layers not divisible into {n_stages} pipeline "
                f"stages; pass layer_fn= for an uneven split "
                f"(remainder layers go to the last stage, see "
                f"partition_layers) or use the MPMD pipeline "
                f"(train/pipeline.py), where ragged stages are free")
        return p.reshape(n_stages, L // n_stages, *p.shape[1:])
    return jax.tree_util.tree_map(reshape, layer_params)


def split_stages_padded(layer_params: Any, n_stages: int):
    """Uneven-split stacking for the SPMD schedules: leaves (L, ...)
    -> (S, kmax, ...) zero-padded per stage, plus the per-stage valid
    counts. Padded slots are masked to IDENTITY inside the per-layer
    scan (`_make_stage_call`), so every shard runs the same program
    shape while stages apply different layer counts."""
    leaves = jax.tree_util.tree_leaves(layer_params)
    if not leaves:
        raise ValueError("layer_params has no leaves")
    L = leaves[0].shape[0]
    parts = partition_layers(L, n_stages)
    kmax = max(c for _, c in parts)

    def stack(p):
        rows = []
        for start, count in parts:
            block = p[start:start + count]
            if count < kmax:
                pad = jnp.zeros((kmax - count,) + p.shape[1:], p.dtype)
                block = jnp.concatenate([block, pad], axis=0)
            rows.append(block)
        return jnp.stack(rows)
    import numpy as np
    return (jax.tree_util.tree_map(stack, layer_params),
            np.asarray([c for _, c in parts], dtype=np.int32))


def _unpad_stage_axis(stacked: Any, layer_params: Any,
                      n_stages: int) -> Any:
    """Inverse of split_stages_padded along the layer axis: (S, kmax,
    ...) -> (L, ...) dropping the padded rows (used to return grads in
    the caller's layer-major layout)."""
    leaves = jax.tree_util.tree_leaves(layer_params)
    parts = partition_layers(leaves[0].shape[0], n_stages)
    return jax.tree_util.tree_map(
        lambda g: jnp.concatenate(
            [g[s, :count] for s, (_, count) in enumerate(parts)],
            axis=0),
        stacked)


def _make_stage_call(stage_fn, layer_fn, counts):
    """Uniform per-stage apply: ``call(params, x, stage, consts)``.

    stage_fn mode (even splits): the caller's whole-sub-stack function,
    unchanged. layer_fn mode (uneven splits): a masked per-layer scan —
    ``layer_fn(one_layer_params, x, *consts) -> x`` is applied to every
    padded slot, and slots past this stage's valid count pass the
    activation through unchanged (`where` keeps the program shape
    identical across shards; grads through padded slots are exactly
    zero because the output disconnects from them)."""
    if layer_fn is None:
        if stage_fn is None:
            raise ValueError("pass stage_fn or layer_fn")
        return lambda p, x, stage, consts: stage_fn(p, x, *consts)
    counts = jnp.asarray(counts, jnp.int32)

    def call(p, x, stage, consts):
        n_valid = counts[stage]

        def body(carry, layer):
            i, xx = carry
            y = layer_fn(layer, xx, *consts)
            return (i + 1, jnp.where(i < n_valid, y, xx)), None
        (_, out), _ = lax.scan(body, (jnp.int32(0), x), p)
        return out
    return call


def _stack_for(mesh_stages: int, layer_params: Any, layer_fn):
    """(stacked pytree, stage_call counts) for either calling mode."""
    if layer_fn is None:
        return split_stages(layer_params, mesh_stages), None
    return split_stages_padded(layer_params, mesh_stages)


def pipeline_apply(mesh: Mesh,
                   stage_fn: Callable[..., jax.Array],
                   layer_params: Any,
                   x: jax.Array,
                   num_microbatches: int,
                   consts: tuple = (),
                   layer_fn: Callable[..., jax.Array] = None) -> jax.Array:
    """Run `stage_fn(stage_params, x_microbatch, *consts)` (one stage's
    layer stack applied to one microbatch) over the pp axis with a
    GPipe schedule.

    x: (batch, ...) activations; `consts` are stage-invariant arrays
    (e.g. rope caches) passed explicitly — closures over tracers don't
    cross the shard_map boundary. Returns x's shape, replicated over pp
    (downstream ops run outside the manual region).

    Uneven layer counts: pass ``layer_fn(one_layer_params, x, *consts)
    -> x`` INSTEAD of stage_fn. The stack is padded to ceil(L/S) per
    stage (remainder layers on the last stage, `partition_layers`) and
    a masked per-layer scan keeps padded slots identity, so L need not
    divide the stage count.

    NOTE: call this under an outer jit (the normal train step). The
    inner jit below exists so EAGER callers work at all (partial-manual
    shard_map only lowers under jit), but eager callers re-trace per
    call — fine for debugging, wrong for a training loop.
    """
    n_stages = mesh.shape["pp"]
    if n_stages <= 1:
        raise ValueError("pipeline_apply needs a pp axis > 1")
    M = num_microbatches
    b = x.shape[0]
    if b % M:
        raise ValueError(f"batch {b} not divisible into {M} microbatches")
    micro = x.reshape(M, b // M, *x.shape[1:])
    stacked, counts = _stack_for(n_stages, layer_params, layer_fn)
    stage_call = _make_stage_call(stage_fn, layer_fn, counts)

    @functools.partial(
        jax.shard_map, mesh=mesh, axis_names={"pp"},
        in_specs=(jax.tree_util.tree_map(lambda _: P("pp"), stacked),
                  P(), jax.tree_util.tree_map(lambda _: P(),
                                              tuple(consts))),
        out_specs=P(), check_vma=False)
    def run(stacked_local, micro_local, consts_local):
        params_local = jax.tree_util.tree_map(lambda p: p[0],
                                              stacked_local)
        stage = lax.axis_index("pp")
        state = jnp.zeros_like(micro_local[0])
        outputs = jnp.zeros_like(micro_local)
        ticks = M + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(t, carry):
            state, outputs = carry
            # stage 0 injects microbatch t (clamped; the tail ticks feed
            # it stale data whose results never reach an emit slot)
            inject = lax.dynamic_index_in_dim(
                micro_local, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            x_in = jnp.where(stage == 0, inject, state)
            y = stage_call(params_local, x_in, stage, consts_local)
            # last stage emits microbatch t-(S-1) once the fill ends
            out_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            emit = jnp.logical_and(stage == n_stages - 1,
                                   t >= n_stages - 1)
            cur = lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                           keepdims=False)
            outputs = lax.dynamic_update_index_in_dim(
                outputs, jnp.where(emit, y, cur), out_idx, 0)
            # rotate activations to the next stage
            state = lax.ppermute(y, "pp", perm)
            return state, outputs

        _, outputs = lax.fori_loop(0, ticks, tick, (state, outputs))
        # broadcast the last stage's outputs to every pp shard (sum of
        # one non-zero contribution)
        outputs = lax.psum(
            jnp.where(stage == n_stages - 1, outputs,
                      jnp.zeros_like(outputs)), "pp")
        return outputs

    # partial-manual shard_map only lowers under jit; wrapping here keeps
    # eager callers (model.loss outside jit) working — jit-in-jit is a
    # no-op when the caller already traces.
    out = jax.jit(run)(stacked, micro, tuple(consts))
    return out.reshape(b, *x.shape[1:])


def pipeline_grads_1f1b(mesh: Mesh,
                        stage_fn: Callable[..., jax.Array],
                        loss_fn: Callable[[jax.Array, jax.Array],
                                          jax.Array],
                        layer_params: Any,
                        x: jax.Array,
                        targets: jax.Array,
                        num_microbatches: int,
                        consts: tuple = (),
                        layer_fn: Callable[..., jax.Array] = None):
    """One-forward-one-backward pipeline schedule (the reference's
    dag_node_operation.py builds exactly this ordering for its NCCL
    actor pipelines; Narayanan et al. PipeDream-Flush / Megatron-LM).

    Unlike GPipe-then-autodiff — which must keep ALL M microbatch
    activations live until the loss — the backward of microbatch m
    starts as soon as its forward leaves the last stage, so each stage
    stores at most 2(S-1)+1 stage-input activations (a static ring XLA
    allocates ONCE) independent of M; stage backwards recompute their
    forward from the saved input (remat), the standard trade.

    Per global tick t (clock-driven SPMD emulation, T = M + 2(S-1)
    ticks), stage s runs the forward of microbatch t-s and the backward
    of microbatch t-2(S-1)+s when those indices are in range; the last
    stage computes the per-microbatch loss + output cotangent in the
    same tick its forward completes, activations ppermute up the pp
    ring while cotangents ppermute down.

    Returns (mean loss over all microbatches, grads in the layer-major
    (L, ...) layout of `layer_params`). stage_fn/loss_fn as in
    pipeline_apply, with loss_fn(y_microbatch, target_microbatch) ->
    scalar summed loss for that microbatch. Uneven layer counts: pass
    ``layer_fn`` instead of stage_fn (see pipeline_apply) — grads come
    back unpadded in the caller's (L, ...) layout either way.
    """
    n_stages = mesh.shape["pp"]
    if n_stages <= 1:
        raise ValueError("pipeline_grads_1f1b needs a pp axis > 1")
    S = n_stages
    M = num_microbatches
    b = x.shape[0]
    if b % M:
        raise ValueError(f"batch {b} not divisible into {M} microbatches")
    micro = x.reshape(M, b // M, *x.shape[1:])
    tmicro = targets.reshape(M, b // M, *targets.shape[1:])
    stacked, counts = _stack_for(n_stages, layer_params, layer_fn)
    stage_call = _make_stage_call(stage_fn, layer_fn, counts)
    A = min(M, 2 * (S - 1) + 1)       # activation ring slots per stage

    @functools.partial(
        jax.shard_map, mesh=mesh, axis_names={"pp"},
        in_specs=(jax.tree_util.tree_map(lambda _: P("pp"), stacked),
                  P(), P(),
                  jax.tree_util.tree_map(lambda _: P(), tuple(consts))),
        out_specs=(P(),
                   jax.tree_util.tree_map(lambda _: P("pp"), stacked)),
        check_vma=False)
    def run(stacked_local, micro_local, tmicro_local, consts_local):
        params_local = jax.tree_util.tree_map(lambda p: p[0],
                                              stacked_local)
        stage = lax.axis_index("pp")
        last = S - 1
        up = [(i, (i + 1) % S) for i in range(S)]
        down = [(i, (i - 1) % S) for i in range(S)]

        def fwd_only(p, xx):
            return stage_call(p, xx, stage, consts_local)

        zero_act = jnp.zeros_like(micro_local[0])
        ring0 = jnp.zeros((A,) + zero_act.shape, zero_act.dtype)
        grads0 = jax.tree_util.tree_map(jnp.zeros_like, params_local)
        T = M + 2 * (S - 1)

        def tick(t, carry):
            fwd_carry, bwd_carry, ring, grads, loss_acc = carry
            # ---------- forward half-tick
            m_f = t - stage
            do_fwd = jnp.logical_and(m_f >= 0, m_f < M)
            m_f_c = jnp.clip(m_f, 0, M - 1)
            inject = lax.dynamic_index_in_dim(micro_local, m_f_c, 0,
                                              keepdims=False)
            x_in = jnp.where(stage == 0, inject, fwd_carry)
            y = fwd_only(params_local, x_in)
            ring = lax.dynamic_update_index_in_dim(
                ring, jnp.where(do_fwd, x_in, ring[m_f_c % A]),
                m_f_c % A, 0)
            # last stage: per-microbatch loss + output cotangent NOW
            tgt = lax.dynamic_index_in_dim(tmicro_local, m_f_c, 0,
                                           keepdims=False)
            loss_m, dLdy = jax.value_and_grad(loss_fn)(y, tgt)
            take_loss = jnp.logical_and(stage == last, do_fwd)
            loss_acc = loss_acc + jnp.where(take_loss, loss_m, 0.0)
            # ---------- backward half-tick
            m_b = t - 2 * (S - 1) + stage
            do_bwd = jnp.logical_and(m_b >= 0, m_b < M)
            m_b_c = jnp.clip(m_b, 0, M - 1)
            x_saved = lax.dynamic_index_in_dim(ring, m_b_c % A, 0,
                                               keepdims=False)
            # last stage consumes its own fresh cotangent (its bwd of m
            # shares the tick with its fwd of m); others take the grad
            # arriving from the next stage
            cot = jnp.where(stage == last, dLdy, bwd_carry)
            _, vjp = jax.vjp(fwd_only, params_local, x_saved)
            dparams, dx = vjp(cot)
            grads = jax.tree_util.tree_map(
                lambda g, d: g + jnp.where(do_bwd, d, 0.0), grads,
                dparams)
            # ---------- communication
            fwd_carry = lax.ppermute(y, "pp", up)
            bwd_carry = lax.ppermute(jnp.where(do_bwd, dx,
                                               jnp.zeros_like(dx)),
                                     "pp", down)
            return fwd_carry, bwd_carry, ring, grads, loss_acc

        _, _, _, grads, loss_acc = lax.fori_loop(
            0, T, tick, (zero_act, zero_act, ring0, grads0,
                         jnp.zeros((), x.dtype)))
        # total loss lives on the last stage only; returned loss is the
        # microbatch mean, so grads scale by 1/M to match d(loss)/dp
        loss = lax.psum(jnp.where(stage == last, loss_acc, 0.0), "pp")
        grads = jax.tree_util.tree_map(lambda g: g[None] / M, grads)
        return loss / M, grads

    loss, stacked_grads = jax.jit(run)(stacked, micro, tmicro,
                                       tuple(consts))
    if layer_fn is None:
        grads = jax.tree_util.tree_map(
            lambda g, p: g.reshape(p.shape), stacked_grads, layer_params)
    else:
        grads = _unpad_stage_axis(stacked_grads, layer_params, S)
    return loss, grads
