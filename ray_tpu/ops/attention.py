"""Flash attention: Pallas TPU kernels, forward AND backward.

The hot op of the model zoo. Forward is an online-softmax kernel that
streams K/V blocks through VMEM on a (batch, head, q-block, k-block)
grid — O(seq) memory, causal blocks above the diagonal skipped. Backward
is ONE kernel on a (b, h, k-block, q-block) grid that computes a block
pair's scores once, TRANSPOSED (block_k, block_q) so the per-row stats
(lse, delta) broadcast along sublanes, and takes all three gradients from
them, five matmuls a pair: dK/dV are summed in VMEM over the inner axis,
a head's whole dQ (float32) over both inner axes and written once, so no
partial sum goes through HBM. A pair below the diagonal carries no
mask; one the diagonal cuts is walked in squares of DIAG_BLOCK, those above
it left out. `_flash_bwd_xla` (a blockwise lax.scan) is the cross-check.

Layout: (batch, num_heads, seq, head_dim). GQA supported: K/V may have
fewer heads (num_kv_heads must divide num_heads) — the kernel maps query
head h to kv head h // (num_heads // num_kv_heads) in the BlockSpec
index map, no materialised repeat. The forward takes values of another
width than the keys (the output is as wide as the values); the backward
kernel does not.

The public `flash_attention` compiles the kernels whenever the target
platform is a TPU (`ops.dispatch.on_tpu`), never the interpreter; off
TPU it is the reference einsum, and tests reach the kernels through
the Pallas interpreter with `flash_attention_kernel`.

GSPMD cannot partition a Mosaic kernel, so every entry point takes the
`mesh` the computation is sharded over and runs the kernel calls —
`_flash_fwd` and `_flash_bwd_pallas`, not the AD wrappers around them,
so a remat policy still sees the named residuals — inside
`jax.shard_map` with the specs of `ops.dispatch.attention_specs`.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.dispatch import attention_specs, on_tpu, shard_kernel

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)

# The kernels' names on the device's clock. The compiler names a
# custom-call instruction after the innermost component of JAX's name
# stack, and an `XLA Ops` event of a profiler trace is called after the
# instruction: with `name=` that is the kernel's own name, where it used
# to be the enclosing call's (`closed_call`, `checkpoint`,
# `rematted_computation`). A transform wraps the first scope opened
# under it (`jvp(flash_fwd)` compiles to `jvp_flash_fwd_`): the caller's
# region (`models/regions.py`) is that scope and takes the wrapping, and
# the region a kernel lies in is its caller's to say. The benchmark's
# roofline metrics find the kernels by these names; a forward recomputed
# under remat is still KERNEL_FWD.
KERNEL_FWD = "flash_fwd"
KERNEL_BWD_DKDV = "flash_bwd_dkdv"
# (the whole backward, dQ too, since PR 55: the name is the benchmark's)
# the forward with a sliding window (forward only: serving's prefill), under
# a name of its own that a reader looking for KERNEL_FWD does not match
KERNEL_WINDOW_FWD = "flash_window_fwd"


# ------------------------------------------------------------- reference
def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True,
                  sm_scale: Optional[float] = None,
                  bias: Optional[jax.Array] = None,
                  window: Optional[int] = None) -> jax.Array:
    """Plain einsum attention; ground truth + CPU path.

    q: (b, h, s, d); k/v: (b, kvh, s, d) with kvh | h. `window`: query i
    sees the `window` keys i - window + 1 .. i.
    """
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if bias is not None:
        logits = logits + bias
    if causal:
        qi = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        ki = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        logits = jnp.where(qi >= ki, logits, DEFAULT_MASK_VALUE)
        if window is not None:
            logits = jnp.where(qi - ki < window, logits, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# ----------------------------------------------------------- forward krn
def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *,
                      sm_scale: float, causal: bool,
                      block_q: int, block_k: int, seq_k: int,
                      window: Optional[int] = None):
    i = pl.program_id(2)           # q block
    j = pl.program_id(3)           # k block
    nk = pl.num_programs(3)
    jg = j                         # this step's place in the grid
    if window is not None:
        # the grid walks only the key blocks a query block's windows
        # reach: j counts from the first of them (`_window_first_block`)
        j = _window_first_block(i, block_q, block_k, window) + j

    @pl.when(jg == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Causal: skip blocks strictly above the diagonal.
    run = (not causal) or (j * block_k <= i * block_q + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (bq, bk)
        ki = j * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            qi = i * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(qi >= ki, s, DEFAULT_MASK_VALUE)
            if window is not None:      # the window's lower edge
                s = jnp.where(qi - ki < window, s, DEFAULT_MASK_VALUE)
        if seq_k % block_k:
            # tail K block: mask padding columns past the true length,
            # and zero V's padding rows — they hold garbage and p=0
            # does not neutralise NaN (0 * NaN = NaN).
            s = jnp.where(ki < seq_k, s, DEFAULT_MASK_VALUE)
            vrows = j * block_k + lax.broadcasted_iota(
                jnp.int32, v.shape, 0)
            v = jnp.where(vrows < seq_k, v, 0)
        m_prev = m_ref[:, :1]                      # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)            # rescale factor
        p = jnp.exp(s - m_new)                     # (bq, bk)
        if window is not None:
            # a row whose window has not reached this block yet has seen
            # nothing: its maximum is still the mask's value, and exp(0)
            # would count the masked keys
            p = jnp.where(s > DEFAULT_MASK_VALUE, p, 0.0)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(jg == nk - 1)
    def _final():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(safe_l)            # (bq, 1)
        # lse laid out (b, h, 8, sq): an (8, block_q) block keeps the
        # last-two-dims (8, 128) Mosaic tiling rule; sublanes broadcast.
        lse_ref[0, 0, :, :] = jnp.broadcast_to(lse[:, 0][None, :],
                                               (8, lse.shape[0]))


def _window_first_block(i, block_q: int, block_k: int, window: int):
    """The first key block that query block i's windows reach."""
    return jnp.maximum(i * block_q - (window - 1), 0) // block_k


def _stat_spec(spec_q: P) -> P:
    """Spec of the per-row statistic (b, h, s) beside a (b, h, s, d)."""
    return P(*spec_q[:3])


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
               mesh=None):
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    dv = v.shape[-1]        # values may be narrower than keys (forward only)
    if h % kvh:
        raise ValueError(
            f"num_heads ({h}) must be a multiple of num_kv_heads ({kvh})")
    if mesh is not None:
        spec_q, spec_kv, _ = attention_specs(mesh, h, kvh)
        return shard_kernel(
            lambda q_, k_, v_: _flash_fwd(q_, k_, v_, causal, sm_scale,
                                          block_q, block_k, interpret),
            mesh, (spec_q, spec_kv, spec_kv),
            (spec_q, _stat_spec(spec_q)))(q, k, v)
    group = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    grid = (b, h, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k))
    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_k=sk)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, 8, block_q),
                         lambda b_, h_, i, j: (b_, h_, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, 8, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),    # acc
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running sum
        ],
        interpret=interpret,
        name=KERNEL_FWD,
    )
    out, lse = call(q, k, v)
    return out, lse[:, :, 0, :]


def _flash_window_fwd(q, k, v, sm_scale, block_q, block_k, interpret,
                      window: int):
    """Causal forward in which query i sees keys i - window + 1 .. i: the
    forward kernel on a grid that holds, a query block, only the key blocks
    its windows reach (those wholly below are never copied in; the blocks
    at the window's two edges are masked). One device, sq == sk."""
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    if sq != sk or window < 1 or h % kvh:
        raise ValueError(
            f"a window of {window} over {sq} queries and {sk} keys, {h} "
            f"heads over {kvh}: self-attention, a window >= 1 and heads a "
            f"multiple of the kv heads only")
    group = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nk = pl.cdiv(sk, block_k)
    # key blocks between the lowest window's first key and the diagonal's
    # last: at most this many, whatever the query block
    reach = min(nk, (window - 1 + block_q - 1) // block_k + 2)

    def kv_block(b_, h_, i, j):
        # past the diagonal the kernel skips: stay on the diagonal's block,
        # which is not copied in again
        last = (i * block_q + block_q - 1) // block_k
        first = _window_first_block(i, block_q, block_k, window)
        return (b_, h_ // group, jnp.minimum(first + j, last), 0)

    call = pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel, sm_scale=sm_scale, causal=True,
            block_q=block_q, block_k=block_k, seq_k=sk, window=window),
        grid=(b, h, pl.cdiv(sq, block_q), reach),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_block),
            pl.BlockSpec((1, 1, block_k, d), kv_block),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, 8, block_q),
                         lambda b_, h_, i, j: (b_, h_, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, 8, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),     # acc
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running sum
        ],
        interpret=interpret,
        name=KERNEL_WINDOW_FWD,
    )
    out, lse = call(q, k, v)
    return out, lse[:, :, 0, :]


# ---------------------------------------------------- backward (pallas)
# A block pair that the causal mask cuts is walked in squares this wide, and
# the squares above the diagonal are not computed (the sweep: PERF.md, PR 55).
DIAG_BLOCK = 512


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                      dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc, *,
                      sm_scale: float, causal: bool, block_q: int,
                      block_k: int, seq_q: int, seq_k: int, diag: int):
    j = pl.program_id(2)           # k block
    i = pl.program_id(3)           # q block (inner)
    nk, nq = pl.num_programs(2), pl.num_programs(3)

    @pl.when((j == 0) & (i == 0))
    def _init_dq():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(i == 0)
    def _init_dkdv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def pair(kr: slice, qr: slice, masked: bool):
        """The three gradients' terms of the keys `kr` and the queries
        `qr` of this step's blocks; `masked`: the diagonal runs through
        them."""
        q, do = q_ref[0, 0, qr, :], do_ref[0, 0, qr, :]
        k, v = k_ref[0, 0, kr, :], v_ref[0, 0, kr, :]
        lse = lse_ref[0, 0, 0:1, qr]           # (1, queries) f32
        dlt = dlt_ref[0, 0, 0:1, qr]
        k0, q0 = j * block_k + kr.start, i * block_q + qr.start
        tail_q, tail_k = seq_q % block_q, seq_k % block_k
        valid = None
        if masked or tail_q or tail_k:
            shape = (k.shape[0], q.shape[0])
            rows = k0 + lax.broadcasted_iota(jnp.int32, shape, 0)
            cols = q0 + lax.broadcasted_iota(jnp.int32, shape, 1)
            valid = (rows <= cols) if masked else True
        if tail_q:
            # q/do padding rows hold garbage and are CONTRACTED into
            # dk/dv below — zero them (p=0 does not neutralise NaN).
            qrows = q0 + lax.broadcasted_iota(jnp.int32, q.shape, 0)
            q = jnp.where(qrows < seq_q, q, 0)
            do = jnp.where(qrows < seq_q, do, 0)
            valid &= cols < seq_q
        if tail_k:
            # k padding rows are contracted into dq — zero the garbage.
            krows = k0 + lax.broadcasted_iota(jnp.int32, k.shape, 0)
            k = jnp.where(krows < seq_k, k, 0)
            valid &= rows < seq_k
        # Transposed scores: rows = k positions, cols = q positions.
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        pt = jnp.exp(st - lse)
        if valid is not None:
            pt = jnp.where(valid, pt, 0.0)
        dv_acc[kr, :] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (keys, d)
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dst = pt * (dpt - dlt) * sm_scale
        if valid is not None:                  # kill 0*inf NaNs from tails
            dst = jnp.where(valid, dst, 0.0)
        dst = dst.astype(q.dtype)
        dk_acc[kr, :] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (keys, d)
        dq_acc[i, qr, :] += jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (queries, d)

    whole_k, whole_q = slice(0, block_k), slice(0, block_q)
    if not causal:
        pair(whole_k, whole_q, masked=False)
    else:
        # no key of the pair lies past any of its queries: no mask
        below = i * block_q >= j * block_k + block_k - 1

        @pl.when(below)
        def _below():
            pair(whole_k, whole_q, masked=False)

        @pl.when((i * block_q + block_q - 1 >= j * block_k) & ~below)
        def _diagonal():
            if block_q != block_k or block_q % diag:
                pair(whole_k, whole_q, masked=True)
                return
            # blocks alike: this is pair (j, j), and which of its squares
            # the diagonal cuts or leaves out is known here
            for a in range(block_k // diag):
                for c in range(a, block_q // diag):
                    pair(slice(a * diag, (a + 1) * diag),
                         slice(c * diag, (c + 1) * diag), masked=a == c)

    @pl.when(i == nq - 1)
    def _final_dkdv():
        dk_ref[0, 0, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when((j == nk - 1) & (i == nq - 1))
    def _final_dq():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, sm_scale,
                      block_q, block_k, interpret, mesh=None,
                      diag_block=DIAG_BLOCK):
    """Full Pallas backward: returns (dq, dk, dv)."""
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    if mesh is not None:
        spec_q, spec_kv, _ = attention_specs(mesh, h, kvh)
        stat = _stat_spec(spec_q)

        def local(*a):
            dq, dk, dv = _flash_bwd_pallas(*a, causal, sm_scale, block_q,
                                           block_k, interpret,
                                           diag_block=diag_block)
            if spec_kv[1] != spec_q[1]:
                # MQA: the one kv head is replicated over tp and each
                # device saw only its own q heads
                dk, dv = lax.psum((dk, dv), spec_q[1])
            return dq, dk, dv

        return shard_kernel(
            local, mesh, (spec_q, spec_kv, spec_kv, spec_q, stat, spec_q),
            (spec_q, spec_kv, spec_kv))(q, k, v, o, lse, do)
    group = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq, nk = pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                  # (b, h, sq)
    # Sublane-broadcast stats layout (b, h, 8, sq): tiles (8, block_q)
    # satisfy Mosaic's (8, 128) rule; kernels read row 0 as (1, block_q).
    lse8 = jnp.broadcast_to(lse[:, :, None, :], (b, h, 8, sq))
    dlt8 = jnp.broadcast_to(delta[:, :, None, :], (b, h, 8, sq))

    def q_block(j, i):
        # a step above the diagonal does nothing: it stays on the first
        # query block that runs, which is then not copied in again
        return jnp.maximum(i, j * block_k // block_q) if causal else i

    # what the kernel holds: dq of a head whole (float32) and its copy
    # out (twice, as every block), six blocks in and two out twice, the
    # two sums, and a pair's scores, their exponentials and two products
    held = (nq * block_q * d * (4 + 2 * q.dtype.itemsize)
            + 2 * 6 * max(block_q, block_k) * d * 4
            + 6 * block_q * block_k * 4)
    dkdv_out_dtype = jnp.float32 if group > 1 else k.dtype
    call = pl.pallas_call(
        functools.partial(
            _flash_bwd_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_q=sq, seq_k=sk,
            diag=diag_block),
        grid=(b, h, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, j, i: (b_, h_, q_block(j, i), 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, j, i: (b_, h_ // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, j, i: (b_, h_ // group, j, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, j, i: (b_, h_, q_block(j, i), 0)),
            pl.BlockSpec((1, 1, 8, block_q),
                         lambda b_, h_, j, i: (b_, h_, 0, q_block(j, i))),
            pl.BlockSpec((1, 1, 8, block_q),
                         lambda b_, h_, j, i: (b_, h_, 0, q_block(j, i))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, j, i: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, j, i: (b_, h_, j, 0)),
            # a head's whole dq, block by block: it stays in VMEM over
            # the two inner axes and is written once
            pl.BlockSpec((1, 1, nq, block_q, d),
                         lambda b_, h_, j, i: (b_, h_, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), dkdv_out_dtype),
            jax.ShapeDtypeStruct((b, h, sk, d), dkdv_out_dtype),
            jax.ShapeDtypeStruct((b, h, nq, block_q, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((nq, block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=max(held + (8 << 20), 32 << 20)),
        interpret=interpret,
        name=KERNEL_BWD_DKDV,
    )
    dk, dv, dq = call(q, k, v, do, lse8, dlt8)
    if group > 1:
        dk = dk.reshape(b, kvh, group, sk, d).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(b, kvh, group, sk, d).sum(axis=2).astype(v.dtype)
    # a tail block's padding rows go
    dq = dq.reshape(b, h, nq * block_q, d)[:, :, :sq]
    return dq, dk, dv


# ------------------------------------------------ backward (xla check)
def _flash_bwd_xla(q, k, v, o, lse, do, causal, sm_scale, block_k):
    """Blockwise flash backward: scan over K blocks; O(seq·block) memory."""
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    group = h // kvh
    if group != 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    # Keep matmul operands in the input dtype (bf16 on TPU) with f32
    # accumulation — upcasting operands would force f32 MXU passes.
    kf, vf = k, v
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)  # (b,h,sq)

    block_k = min(block_k, sk)
    sk_pad = ((sk + block_k - 1) // block_k) * block_k
    if sk_pad != sk:
        pad = [(0, 0), (0, 0), (0, sk_pad - sk), (0, 0)]
        kf = jnp.pad(kf, pad)
        vf = jnp.pad(vf, pad)
    nk = sk_pad // block_k
    kb = kf.reshape(b, h, nk, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = vf.reshape(b, h, nk, block_k, d).transpose(2, 0, 1, 3, 4)
    qi = lax.broadcasted_iota(jnp.int32, (sq, block_k), 0)

    def step(dq, blk):
        j, k_j, v_j = blk                                  # (b,h,bk,d)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_j,
                       preferred_element_type=jnp.float32) * sm_scale
        ki = j * block_k + lax.broadcasted_iota(
            jnp.int32, (sq, block_k), 1)
        valid = ki < sk
        if causal:
            valid = valid & (qi >= ki)
        if causal or sk_pad != sk:
            s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lse[..., None])                    # (b,h,sq,bk) f32
        pc = p.astype(q.dtype)
        dv_j = jnp.einsum("bhqk,bhqd->bhkd", pc, do,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do, v_j,
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[..., None]) * sm_scale).astype(q.dtype)
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, k_j,
                             preferred_element_type=jnp.float32)
        dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, q,
                          preferred_element_type=jnp.float32)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros(q.shape, jnp.float32)  # f32 accumulator across blocks
    dq, (dkb, dvb) = lax.scan(
        step, dq0, (jnp.arange(nk), kb, vb))
    dk = dkb.transpose(1, 2, 0, 3, 4).reshape(b, h, sk_pad, d)[:, :, :sk]
    dv = dvb.transpose(1, 2, 0, 3, 4).reshape(b, h, sk_pad, d)[:, :, :sk]
    if group != 1:
        dk = dk.reshape(b, kvh, group, sk, d).sum(axis=2)
        dv = dv.reshape(b, kvh, group, sk, d).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(q.dtype), dv.astype(q.dtype)


# ----------------------------------------------------------- public API
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret, mesh):
    """Returns (out, lse); lse has stop-gradient semantics (its cotangent
    is ignored by the VJP — it is an auxiliary statistic, not a loss
    term)."""
    return _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                      interpret, mesh)


def _flash_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                    mesh):
    out, lse = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                          interpret, mesh)
    return (out, lse), (q, k, v, out, lse)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, interpret, mesh,
                    res, g):
    do, _g_lse = g  # lse cotangent dropped by design (see _flash docstring)
    q, k, v, out, lse = res
    if v.shape[-1] != q.shape[-1]:
        raise NotImplementedError(
            f"the flash backward wants keys ({q.shape[-1]} wide) and values "
            f"({v.shape[-1]}) alike: only the forward takes unlike widths")
    return _flash_bwd_pallas(q, k, v, out, lse, do, causal, sm_scale,
                             block_q, block_k, interpret, mesh)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _repeat_kv_for(mesh, q, k, v):
    """GQA whose kv heads do not split over the mesh's tp axis: repeat
    K/V to q's head count (see attention_specs). Done out here, in
    differentiable jnp, so the kernels never see the case."""
    if mesh is not None and attention_specs(mesh, q.shape[1],
                                            k.shape[1])[2]:
        rep = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    return k, v


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    return_lse: bool = False, mesh=None,
                    window: Optional[int] = None):
    """Dispatching entry point: the compiled Pallas kernels when the
    target platform is a TPU, the einsum reference elsewhere.

    Shapes: q (b, h, s, d); k (b, kvh, s, d) and v (b, kvh, s, dv), kvh |
    h; `dv` may differ from `d` in the forward (latent attention's values
    are narrower than its keys), the backward wants them alike. `mesh`: the
    mesh of more than one device the operands are sharded over
    (`ops.dispatch.kernel_mesh`), or None. `window`: causal attention in
    which a query sees its last `window` keys, itself among them; forward
    only (a prefill), on one device.
    """
    if window is not None:
        if not causal or mesh is not None or return_lse:
            raise ValueError("a window is causal, forward only and on one "
                             "device")
        if on_tpu():
            return flash_window_attention_kernel(q, k, v, window, sm_scale,
                                                 block_q, block_k)
        return mha_reference(q, k, v, sm_scale=sm_scale, window=window)
    if return_lse or on_tpu():
        out, lse = _flash_kernel(q, k, v, causal, sm_scale, block_q,
                                 block_k, mesh)
        return (out, lse) if return_lse else out
    return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)


def _flash_kernel(q, k, v, causal, sm_scale, block_q, block_k, mesh):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    k, v = _repeat_kv_for(mesh, q, k, v)
    # interpret only where there is no TPU to compile for
    return _flash(q, k, v, causal, sm_scale, block_q, block_k,
                  not on_tpu(), mesh)


def flash_attention_kernel(q, k, v, causal=True, sm_scale=None,
                           block_q=128, block_k=128, mesh=None):
    """Force the Pallas kernel path (interpreter off-TPU) — test hook."""
    return _flash_kernel(q, k, v, causal, sm_scale, block_q, block_k,
                         mesh)[0]


def flash_window_attention_kernel(q, k, v, window: int, sm_scale=None,
                                  block_q=128, block_k=128):
    """The windowed forward kernel (interpreter off-TPU) — `flash_attention`
    with a window on a TPU, and the test hook elsewhere."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash_window_fwd(q, k, v, sm_scale, block_q, block_k,
                             not on_tpu(), window)[0]


# --------------------------------------- remat-saveable attention path
#
# Under per-layer `jax.checkpoint`, a custom_vjp flash kernel reruns its
# forward during the backward pass to rebuild residuals — the kernel
# executes twice per step. This path splits the op so the residuals
# (out, lse) are *named public values* a checkpoint policy can save:
#
#   out, lse = fwd kernel        (no AD; pruned from recompute when saved)
#   out, lse = checkpoint_name(...)
#   return _attn_from_saved(q, k, v, stop_grad(out), stop_grad(lse))
#
# `_attn_from_saved` is the only differentiable op: its VJP runs the
# Pallas backward straight from the saved residuals. Cotangents for
# out/lse die at stop_gradient, so the forward kernel is never
# differentiated or (with `save_only_these_names(*ATTN_RESIDUAL_NAMES)`)
# re-executed. q/k/v are the caller's to name: `models.transformer` does,
# and its default policy keeps them too (three matmuls and the rotary a
# layer that the backward then does not run again).

ATTN_RESIDUAL_NAMES = ("attn_out", "attn_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _attn_from_saved(q, k, v, out, lse, causal, sm_scale, block_q,
                     block_k, interpret, mesh):
    return out


def _afs_fwd(q, k, v, out, lse, causal, sm_scale, block_q, block_k,
             interpret, mesh):
    return out, (q, k, v, out, lse)


def _afs_bwd(causal, sm_scale, block_q, block_k, interpret, mesh, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd_pallas(q, k, v, out, lse, do, causal,
                                   sm_scale, block_q, block_k, interpret,
                                   mesh)
    # out/lse arrive through stop_gradient: their cotangents are dropped
    # symbolically, these zeros never materialise.
    return dq, dk, dv, jnp.zeros_like(out), jnp.zeros_like(lse)


_attn_from_saved.defvjp(_afs_fwd, _afs_bwd)


def flash_attention_saveable(q: jax.Array, k: jax.Array, v: jax.Array,
                             causal: bool = True,
                             sm_scale: Optional[float] = None,
                             block_q: int = 128, block_k: int = 128,
                             mesh=None) -> jax.Array:
    """Flash attention whose residuals survive `jax.checkpoint` when the
    wrapping policy keeps ATTN_RESIDUAL_NAMES (see block comment above).
    Semantically identical to `flash_attention`'s kernel path; use
    inside rematted layer bodies."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    interpret = not on_tpu()
    k, v = _repeat_kv_for(mesh, q, k, v)
    from jax.ad_checkpoint import checkpoint_name
    # Run the forward kernel on gradient-stopped inputs: pallas_call has
    # no JVP rule, and the only differentiable route is _attn_from_saved.
    out, lse = _flash_fwd(lax.stop_gradient(q), lax.stop_gradient(k),
                          lax.stop_gradient(v), causal, sm_scale,
                          block_q, block_k, interpret, mesh)
    out = checkpoint_name(out, ATTN_RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, ATTN_RESIDUAL_NAMES[1])
    return _attn_from_saved(q, k, v, lax.stop_gradient(out),
                            lax.stop_gradient(lse), causal, sm_scale,
                            block_q, block_k, interpret, mesh)
