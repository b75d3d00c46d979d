"""The delta rule whose decay is a vector over the key width (KDA: one
`alpha` a head **and key channel**, where `ops.gated_delta` has one a
head), as two Pallas TPU kernels and their plain `jax.numpy` twins. The
convolution, the L2 norm, the triangular inverse and the step kernel's
layout are `ops.gated_delta`'s.

A head keeps a state `S` (key width x value width, float32) a sequence.
With `alpha_t = exp(g_t)`, `g_t` a vector over the key width whose entries
lie in (`lower bound`, 0), and `beta_t` in (0, 1):

    S'_t = Diag(alpha_t) S_{t-1}
    S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
    o_t  = S_t^T q_t

`kda_recurrence` writes that position by position (the tests' ground
truth). By chunks of `CHUNK` positions, `G` the running sum of `g` inside a
chunk (a row a position), the decay between two positions no longer masks
`K K^T`: it lives inside the contraction over the key width,

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)   (j < i);  T = (I + A)^-1
    U = T diag(beta) (V - (K * exp(G)) S_0)
    O = (Q * exp(G)) S_0 + tril(P) U,   P_ij = sum_c q_ic k_jc exp(G_ic - G_jc)
    S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

(`*` elementwise). `kda_chunked` is that in plain float32 `jax.numpy`, the
(chunk, chunk, key width) tensor of decays written out a chunk at a time
(a model's `apply`, differentiable, and the path off the TPU).
`kda_chunk_fwd`, the kernel, cannot hold that tensor and cannot factor it
over a whole chunk either: `exp(-G_jc)` passes float32 after 18 positions
at a decay of -5 a position. It factors a sub-block of `SOLVE_BLOCK` rows
at a time around the sub-block's middle position m: rows `k_i * exp(G_i -
G_m)`, columns `k_j * exp(G_m - G_j)`, one matmul a sub-block of rows
against all columns up to it. Inside the sub-block both exponents lie
within `SOLVE_BLOCK / 2` positions' decay (`MAX_EXPONENT` bounds what a
config may ask for); columns before it have `G_m - G_j <= 0`; columns
after it are masked, their exponent clamped so that nothing is infinite.
The state is kept transposed inside the kernel (value width x key width),
so that the decay to the chunk's end is a row broadcast over sublanes.

Decode (`kda_step`, the kernel; `kda_step_reference`) is
`gated_delta_step` with the decay spread over a head's columns from its
own (key width x heads) matrix, as the key and the query are, where the
scalar decay was a number a column: the pool `(layers, slots + 1, key
width, heads x value width)` float32, aliased in and out, the last slot
nobody's. A padded position has `g = 0`, `beta = 0` and leaves the state
alone; the chunk kernel skips the chunks past a prompt's true length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.dispatch import on_tpu
from ray_tpu.ops.exact import F32, HIGHEST, dot as _dot
# `uses_chunk_kernel`, `uses_step_kernel`: the two kernels take the blocks
# and the pool layout `gated_delta`'s take, so they tile where those do
from ray_tpu.ops.gated_delta import (CHUNK, SOLVE_BLOCK, chunk_heads,
                                     solve_unit_lower, step_columns,
                                     uses_chunk_kernel, uses_step_kernel)

# The kernels' names on the device's clock (see attention.KERNEL_FWD).
KERNEL_CHUNK = "kda_chunk_fwd"
KERNEL_STEP = "kda_step"

# the largest exponent the chunk kernel's factors take: half a sub-block of
# positions at the lower bound of g must stay under it (`lower_bound_fits`)
MAX_EXPONENT = 80.0


def lower_bound_fits(lower_bound: float, block: int = SOLVE_BLOCK) -> bool:
    """Whether a log decay bounded below by `lower_bound` a position keeps
    the chunk kernel's factors finite."""
    return 0.0 > lower_bound >= -MAX_EXPONENT / (block // 2)


def gates(f, b, a_log, dt_bias, lower_bound: float):
    """(g, beta) in float32 from the projections f (..., heads, dk) and b
    (..., heads): `g = lower_bound sigmoid(exp(A_log) (f + dt_bias))`, the
    log of the decay a key channel, in (`lower_bound`, 0) whatever f is
    (`A_log` (heads,), `dt_bias` (heads, dk)); `beta = sigmoid(b)`."""
    rate = jnp.exp(a_log.astype(F32))[:, None]
    g = lower_bound * jax.nn.sigmoid(
        rate * (f.astype(F32) + dt_bias.astype(F32)))
    return g, jax.nn.sigmoid(b.astype(F32))


# ------------------------------------------------------- plain twins
def kda_recurrence(q, k, v, g, beta, state=None):
    """The recurrence position by position. q, k (heads, s, dk), v (heads,
    s, dv), g (heads, s, dk) and beta (heads, s) float32, state (heads, dk,
    dv) float32 or None for zeros. Returns (o (heads, s, dv) float32, the
    last state)."""
    H, _, dk = q.shape
    dv = v.shape[-1]
    if state is None:
        state = jnp.zeros((H, dk, dv), F32)

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[:, :, None]
        mem = jnp.einsum("hkv,hk->hv", S, kt, precision=HIGHEST)
        u = (vt - mem) * bt[:, None]
        S = S + kt[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=HIGHEST)

    xs = tuple(a.astype(F32).swapaxes(0, 1) for a in (q, k, v, g, beta))
    state, o = lax.scan(step, state.astype(F32), xs)
    return o.swapaxes(0, 1), state


def kda_chunked(q, k, v, g, beta, state=None, chunk: int = CHUNK):
    """The same by chunks, in plain float32 `jax.numpy` (differentiable):
    shapes as `kda_recurrence`, s a multiple of `chunk`. A chunk's decays
    between positions are written out, (heads, chunk, chunk, dk), inside
    the scan over chunks."""
    H, s, dk = q.shape
    dv = v.shape[-1]
    n = s // chunk
    if n * chunk != s:
        raise ValueError(f"{s} positions are not whole chunks of {chunk}")
    if state is None:
        state = jnp.zeros((H, dk, dv), F32)
    at_or_below = jnp.tri(chunk, dtype=bool)
    below = jnp.tri(chunk, k=-1, dtype=bool)
    eye = jnp.eye(chunk, dtype=F32)

    def one(S, x):
        q_, k_, v_, g_, b_ = x
        G = jnp.cumsum(g_, axis=1)                      # (H, C, dk)
        decay = jnp.exp(jnp.where(
            at_or_below[None, :, :, None],
            G[:, :, None, :] - G[:, None, :, :], -jnp.inf))
        kk = jnp.einsum("hic,hjc,hijc->hij", k_, k_, decay,
                        precision=HIGHEST)
        A = jnp.where(below, kk, 0.0) * b_[..., None]
        T = jax.scipy.linalg.solve_triangular(
            eye + A, jnp.broadcast_to(eye, A.shape), lower=True)
        eG = jnp.exp(G)
        U = jnp.einsum(
            "hij,hjv->hiv", T, b_[..., None] * (v_ - jnp.einsum(
                "hjc,hcv->hjv", k_ * eG, S, precision=HIGHEST)),
            precision=HIGHEST)
        qk = jnp.einsum("hic,hjc,hijc->hij", q_, k_, decay,
                        precision=HIGHEST)
        o = (jnp.einsum("hic,hcv->hiv", q_ * eG, S, precision=HIGHEST)
             + jnp.einsum("hij,hjv->hiv", qk, U, precision=HIGHEST))
        S = (eG[:, -1, :, None] * S + jnp.einsum(
            "hic,hiv->hcv", k_ * jnp.exp(G[:, -1:] - G), U,
            precision=HIGHEST))
        return S, o

    xs = tuple(a.astype(F32).reshape(H, n, chunk, *a.shape[2:]).swapaxes(
        0, 1) for a in (q, k, v, g, beta))
    state, o = lax.scan(one, state.astype(F32), xs)
    return o.swapaxes(0, 1).reshape(H, s, dv), state


def kda_step_reference(q, k, v, g, beta, pool, layer, slots):
    """One position a lane against the pool, gathered and scattered. q, k
    (B, heads, dk), v (B, heads, dv), g (B, heads, dk) and beta (B, heads)
    float32, pool (layers, slots + 1, dk, heads x dv) float32, slots (B,)
    int32 (-1: an inactive lane, which writes nothing). Returns (o (B,
    heads, dv) float32, pool)."""
    B, H, dk = q.shape
    dv = v.shape[-1]
    n = pool.shape[1]
    where = jnp.where(slots >= 0, slots, n)         # -1: written nowhere
    S = pool[layer, jnp.clip(slots, 0, n - 1)]
    S = S.reshape(B, dk, H, dv).transpose(0, 2, 1, 3)       # (B, H, dk, dv)
    q, k, v = (a.astype(F32) for a in (q, k, v))
    S = S * jnp.exp(g)[..., None]
    mem = jnp.einsum("bhkv,bhk->bhv", S, k, precision=HIGHEST)
    u = (v - mem) * beta[..., None]
    S = S + k[..., None] * u[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", S, q, precision=HIGHEST)
    S = S.transpose(0, 2, 1, 3).reshape(B, dk, H * dv)
    return o, pool.at[layer, where].set(S, mode="drop")


# --------------------------------------------------- the chunk kernel
def _chunk_kernel(len_ref, q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref,
                  *, chunk: int, sub: int):
    """Grid (head blocks, chunks), the chunks in order: the state, held
    transposed (value width x key width), is the output block the chunks of
    a head block share."""
    c = pl.program_id(1)
    heads = q_ref.shape[0]

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    live = c * chunk < len_ref[0]

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _():
        row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        mid = max(sub // 2 - 1, 0)
        for h in range(heads):
            kf = k_ref[h].astype(F32)
            G, bc, St = g_ref[h], b_ref[h, 0], s_ref[h]
            kk, qk = [], []
            for lo in range(0, chunk, sub):
                # rows lo .. lo + sub against every column, both sides
                # scaled around the sub-block's middle position
                at = g_ref[h, lo + mid:lo + mid + 1, :]         # (1, dk)
                rows = jnp.exp(g_ref[h, lo:lo + sub, :] - at)
                cols = kf * jnp.exp(jnp.minimum(at - G, MAX_EXPONENT))
                kk.append(_dot(
                    k_ref[h, lo:lo + sub, :].astype(F32) * rows, cols,
                    ((1,), (1,))))
                qk.append(_dot(
                    q_ref[h, lo:lo + sub, :].astype(F32) * rows, cols,
                    ((1,), (1,))))
            kk = jnp.concatenate(kk, axis=0)
            qk = jnp.concatenate(qk, axis=0)
            T = solve_unit_lower(jnp.where(row > col, kk, 0.0) * bc)
            eg = jnp.exp(G)
            U = (_dot(T, v_ref[h].astype(F32) * bc)
                 - _dot(_dot(T, kf * (bc * eg)), St, ((1,), (1,))))
            o_ref[h] = (
                _dot(q_ref[h].astype(F32) * eg, St, ((1,), (1,)))
                + _dot(jnp.where(row >= col, qk, 0.0), U)).astype(
                    o_ref.dtype)
            # to the chunk's end: exp(G_C - G) a key, exp(G_C) the state's
            # key channels (its lanes here)
            end = g_ref[h, chunk - 1:chunk, :]                  # (1, dk)
            s_ref[h] = (jnp.exp(end) * St
                        + _dot(U, kf * jnp.exp(end - G), ((0,), (0,))))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _chunk_call(q, k, v, g, beta, true_len, chunk: int, interpret: bool):
    H, s, dk = q.shape
    dv = v.shape[-1]
    n = s // chunk
    if n * chunk != s:
        raise ValueError(f"{s} positions are not whole chunks of {chunk}")
    hb = chunk_heads(H)
    G = jnp.cumsum(g.astype(F32).reshape(H, n, chunk, dk),
                   axis=2).reshape(H, s, dk)
    beta = beta.astype(F32).reshape(H, n, chunk, 1)

    def seq(h, c, len_ref):
        # a chunk past the prompt is not copied in: the last live one stays
        return (h, jnp.minimum(c, (len_ref[0] - 1) // chunk), 0)

    def gate(h, c, len_ref):
        return (*seq(h, c, len_ref), 0)

    call = pl.pallas_call(
        functools.partial(_chunk_kernel, chunk=chunk,
                          sub=min(SOLVE_BLOCK, chunk)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // hb, n),
            in_specs=[pl.BlockSpec((hb, chunk, dk), seq),
                      pl.BlockSpec((hb, chunk, dk), seq),
                      pl.BlockSpec((hb, chunk, dv), seq),
                      pl.BlockSpec((hb, chunk, dk), seq),
                      pl.BlockSpec((hb, 1, chunk, 1), gate)],
            out_specs=[pl.BlockSpec((hb, chunk, dv),
                                    lambda h, c, len_ref: (h, c, 0)),
                       pl.BlockSpec((hb, dv, dk),
                                    lambda h, c, len_ref: (h, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct((H, s, dv), q.dtype),
                   jax.ShapeDtypeStruct((H, dv, dk), F32)],
        interpret=interpret,
        name=KERNEL_CHUNK,
    )
    o, state = call(jnp.asarray(true_len, jnp.int32).reshape(1),
                    q, k, v, G, beta)
    return o, state.swapaxes(1, 2)


def _inert_padding(g, beta, true_len):
    """g (heads, s, dk) and beta (heads, s) with the positions from
    `true_len` on made padding: no decay, nothing written."""
    real = (jnp.arange(g.shape[1]) < true_len)[None, :]
    return jnp.where(real[..., None], g, 0.0), jnp.where(real, beta, 0.0)


def kda_prefill(q, k, v, g, beta, true_len, chunk: int = CHUNK):
    """One padded prompt from a zero state: q, k (heads, s, dk), v (heads,
    s, dv) in the activations' dtype, g (heads, s, dk) and beta (heads, s)
    float32, positions `>= true_len` padding (they leave the state alone).
    Returns (o (heads, s, dv) in q's dtype, zeros past the last live chunk
    under the kernel; the state at `true_len` (heads, dk, dv) float32).
    The kernel on a TPU where the shapes tile, the plain chunked form
    elsewhere."""
    g, beta = _inert_padding(g, beta, true_len)
    if uses_chunk_kernel(q.shape[-1], v.shape[-1], chunk, q.dtype):
        return _chunk_call(q, k, v, g, beta, true_len, chunk, False)
    o, state = kda_chunked(q, k, v, g, beta, chunk=chunk)
    return o.astype(q.dtype), state


def kda_prefill_kernel(q, k, v, g, beta, true_len, chunk: int = CHUNK):
    """Force the Pallas kernel path (interpreter off-TPU) — test hook."""
    g, beta = _inert_padding(g, beta, true_len)
    return _chunk_call(q, k, v, g, beta, true_len, chunk, not on_tpu())


# ---------------------------------------------------- the step kernel
def _step_kernel(layer_ref, slot_ref, qt_ref, kt_ref, at_ref, v_ref, b_ref,
                 s_ref, o_ref, s_out_ref, *, dv: int):
    """Grid (lanes, column blocks of the state): the block (dk, cols) of a
    lane's state, `cols` whole heads side by side. q, k and the decay come
    transposed (dk, padded heads) and are spread over their heads' columns
    by a 0/1 matrix; v and beta come spread already (1, cols)."""
    del layer_ref
    b, j = pl.program_id(0), pl.program_id(1)
    cols = s_ref.shape[-1]
    padded = qt_ref.shape[-1]
    S = s_ref[0, 0]

    @pl.when(slot_ref[b] < 0)
    def _():                    # nobody's slot: as it was
        s_out_ref[0, 0] = S
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(slot_ref[b] >= 0)
    def _():
        head = (j * cols + lax.broadcasted_iota(
            jnp.int32, (padded, cols), 1)) // dv
        mine = head == lax.broadcasted_iota(jnp.int32, (padded, cols), 0)
        spread = mine.astype(kt_ref.dtype)
        K = _dot(kt_ref[0], spread)                      # (dk, cols)
        Q = _dot(qt_ref[0], spread)
        Sd = S * _dot(at_ref[0], mine.astype(F32))
        mem = jnp.sum(Sd * K, axis=0, keepdims=True)     # (1, cols)
        new = Sd + K * ((v_ref[0] - mem) * b_ref[0])
        s_out_ref[0, 0] = new
        o_ref[0] = jnp.sum(new * Q, axis=0, keepdims=True)


# jitted for the reason `paged_attention._paged_decode_call` is: traced
# once a program, the layer an argument
@functools.partial(jax.jit, static_argnames=("cols", "interpret"))
def _step_call(q, k, v, g, beta, pool, layer, slots, cols: int,
               interpret: bool):
    B, H, dk = q.shape
    dv = v.shape[-1]
    width = H * dv
    padded = -(-H // 16) * 16           # whole sublanes of a 0/1 matrix
    trash = pool.shape[1] - 1

    def transposed(a):
        a = jnp.pad(a, ((0, 0), (0, padded - H), (0, 0)))
        return a.transpose(0, 2, 1)                      # (B, dk, padded)

    def spread(a):                                       # a number a head
        return jnp.repeat(a.astype(F32), dv, axis=-1)[:, None, :]

    def lane(b, j, layer_ref, slot_ref):
        return (b, 0, j)

    def whole(b, j, layer_ref, slot_ref):
        return (b, 0, 0)

    def state(b, j, layer_ref, slot_ref):
        slot = slot_ref[b]
        return (layer_ref[0], jnp.where(slot < 0, trash, slot), 0, j)

    call = pl.pallas_call(
        functools.partial(_step_kernel, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, width // cols),
            in_specs=[pl.BlockSpec((1, dk, padded), whole),
                      pl.BlockSpec((1, dk, padded), whole),
                      pl.BlockSpec((1, dk, padded), whole),
                      pl.BlockSpec((1, 1, cols), lane),
                      pl.BlockSpec((1, 1, cols), lane),
                      pl.BlockSpec((1, 1, dk, cols), state)],
            out_specs=[pl.BlockSpec((1, 1, cols), lane),
                       pl.BlockSpec((1, 1, dk, cols), state)]),
        out_shape=[jax.ShapeDtypeStruct((B, 1, width), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool (after the two scalar arguments) is the second output
        input_output_aliases={7: 1},
        interpret=interpret,
        name=KERNEL_STEP,
    )
    o, pool = call(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.where(slots < trash, slots, -1).astype(jnp.int32),
        transposed(q), transposed(k),
        transposed(jnp.exp(g.astype(F32))),
        v.astype(F32).reshape(B, 1, width), spread(beta), pool)
    return o.reshape(B, H, dv), pool


def kda_step(q, k, v, g, beta, pool, layer, slots):
    """Dispatching entry point of a decode step's recurrence: the kernel
    on a TPU where the shapes tile (the pool updated in place: donate it),
    gather and scatter elsewhere. Shapes as `kda_step_reference`."""
    H, dk = q.shape[1:]
    dv = v.shape[-1]
    if uses_step_kernel(H, dk, dv):
        return _step_call(q, k, v, g, beta, pool, layer, slots,
                          step_columns(H, dk, dv), False)
    return kda_step_reference(q, k, v, g, beta, pool, layer, slots)


def kda_step_kernel(q, k, v, g, beta, pool, layer, slots):
    """Force the Pallas kernel path (interpreter off-TPU) — test hook: the
    columns a grid step takes are all of them where the shapes do not
    tile."""
    H, dk = q.shape[1:]
    dv = v.shape[-1]
    return _step_call(q, k, v, g, beta, pool, layer, slots,
                      step_columns(H, dk, dv) or H * dv, not on_tpu())
