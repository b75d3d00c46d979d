"""The gated delta rule: a linear-attention layer's recurrence, as two
Pallas TPU kernels and their plain `jax.numpy` twins.

A head keeps a state `S` (key width x value width, float32) a sequence,
whatever the sequence's length. With `alpha_t = exp(g_t)` in (0, 1) and
`beta_t` in (0, 2):

    S'_t = alpha_t S_{t-1}
    S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
    o_t  = S_t^T q_t

`gated_delta_recurrence` writes that position by position (the tests'
ground truth). Prefill computes the same by chunks of `CHUNK` positions
(`gated_delta_chunked`, plain and differentiable: a model's `apply` and
the path off the TPU; `gated_delta_chunk_fwd`, the kernel): with `G` the
running sum of `g` inside a chunk and `D_ij = exp(G_i - G_j)` for j <= i,

    A = tril(diag(beta) (K K^T * D), -1);   T = (I + A)^-1
    U~ = T diag(beta) V;   W = T diag(beta exp(G)) K
    U = U~ - W S_0                          (the state enters once)
    O = diag(exp(G)) Q S_0 + tril(Q K^T * D) U
    S_C = exp(G_C) S_0 + (diag(exp(G_C - G)) K)^T U   (and leaves once)

`T` is a unit lower-triangular inverse: exact forward substitution inside
diagonal blocks of `SOLVE_BLOCK` rows (all blocks at once, one masked
matmul a row), then the blocks joined by the finite Neumann series of a
matrix that is nilpotent over the blocks (`solve_unit_lower`). A padded
position has `g = 0`, `beta = 0`: it leaves the state alone, so the state
after a padded bucket is the state at the prompt's true length, and the
kernel skips the chunks past it.

Decode advances one position a lane (`gated_delta_step`, the kernel;
`gated_delta_step_reference`): the states live in a pool `(layers, slots
+ 1, key width, heads x value width)` float32, a sequence's at the slot
its page table names, the last slot nobody's (a lane that is inactive
reads and writes that one, unchanged). The pool is aliased in and out;
only the slots of active lanes are written. The state is laid out with
the heads side by side along the lanes, so that a pool row is whole
128-lanes with no padding (192 alone would pad to 256), and the update is
elementwise float32 on the VPU under the copies: the kernel is bound by
the bytes of the state, read and written once.

Around both: the L2 norms and the gates, plain `jax.numpy`; the causal
depthwise convolution in front of the rule is `ops/conv.py`'s, its tails
in a pool beside the states, at the same slot.
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

# The kernels' names on the device's clock (see attention.KERNEL_FWD).
KERNEL_CHUNK = "gated_delta_chunk_fwd"
KERNEL_STEP = "gated_delta_step"

CHUNK = 64              # positions a chunk: the family's convention
SOLVE_BLOCK = 16        # rows solved by substitution before blocks join
# heads a grid step of the chunk kernel: their chains of small matmuls are
# independent, so the scheduler fills one's latency with another's
CHUNK_HEADS = 6
# bytes of state a grid step of the step kernel holds (in, out, and the
# temporaries of the update are each this much)
STEP_BLOCK_BYTES = 1 << 20


# ------------------------------------------------ around the recurrence
def l2_normalize(x, eps: float = 1e-6):
    """x / |x| over the last axis, in float32."""
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gates(a, b, a_log, dt_bias, allow_neg_eigval: bool):
    """(g, beta) in float32 from the projections a, b (..., heads):
    `g = -exp(A_log) softplus(a + dt_bias)`, the log of the decay, and
    `beta = sigmoid(b)`, doubled where a transition's eigenvalue `1 -
    beta` may be negative."""
    g = -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(
        a.astype(F32) + dt_bias.astype(F32))
    beta = jax.nn.sigmoid(b.astype(F32))
    return g, 2.0 * beta if allow_neg_eigval else beta


# ------------------------------------------------------- plain twins
def gated_delta_recurrence(q, k, v, g, beta, state=None):
    """The recurrence position by position. q, k (heads, s, dk), v (heads,
    s, dv), g, beta (heads, s) float32, state (heads, dk, dv) float32 or
    None for zeros. Returns (o (heads, s, dv) float32, the last state)."""
    H, _, dk = q.shape
    dv = v.shape[-1]
    if state is None:
        state = jnp.zeros((H, dk, dv), F32)

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[:, None, None]
        mem = jnp.einsum("hkv,hk->hv", S, kt, precision=HIGHEST)
        u = (vt - mem) * bt[:, None]
        S = S + kt[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=HIGHEST)

    xs = tuple(a.astype(F32).swapaxes(0, 1) for a in (q, k, v, g, beta))
    state, o = lax.scan(step, state.astype(F32), xs)
    return o.swapaxes(0, 1), state


def gated_delta_chunked(q, k, v, g, beta, state=None, chunk: int = CHUNK):
    """The same by chunks, in plain float32 `jax.numpy` (differentiable):
    shapes as `gated_delta_recurrence`, s a multiple of `chunk`."""
    H, s, dk = q.shape
    dv = v.shape[-1]
    n = s // chunk
    if n * chunk != s:
        raise ValueError(f"{s} positions are not whole chunks of {chunk}")
    if state is None:
        state = jnp.zeros((H, dk, dv), F32)
    qc, kc, vc = (a.astype(F32).reshape(H, n, chunk, -1) for a in (q, k, v))
    bc = beta.astype(F32).reshape(H, n, chunk)
    G = jnp.cumsum(g.astype(F32).reshape(H, n, chunk), axis=-1)
    at_or_below = jnp.tri(chunk, dtype=bool)
    below = jnp.tri(chunk, k=-1, dtype=bool)
    decay = jnp.exp(jnp.where(at_or_below,
                              G[..., :, None] - G[..., None, :], -jnp.inf))
    kk = jnp.einsum("hnik,hnjk->hnij", kc, kc, precision=HIGHEST)
    A = jnp.where(below, kk * decay, 0.0) * bc[..., None]
    eye = jnp.eye(chunk, dtype=F32)
    T = jax.scipy.linalg.solve_triangular(
        eye + A, jnp.broadcast_to(eye, A.shape), lower=True)
    eG = jnp.exp(G)
    U0 = jnp.einsum("hnij,hnjv->hniv", T, vc * bc[..., None],
                    precision=HIGHEST)
    W = jnp.einsum("hnij,hnjk->hnik", T, kc * (bc * eG)[..., None],
                   precision=HIGHEST)
    qk = jnp.einsum("hnik,hnjk->hnij", qc, kc, precision=HIGHEST) * decay
    k_end = kc * jnp.exp(G[..., -1:] - G)[..., None]

    def one(S, x):
        q_, U0_, W_, qk_, eG_, k_end_ = x
        U = U0_ - jnp.einsum("hik,hkv->hiv", W_, S, precision=HIGHEST)
        o = (eG_[..., None] * jnp.einsum("hik,hkv->hiv", q_, S,
                                         precision=HIGHEST)
             + jnp.einsum("hij,hjv->hiv", qk_, U, precision=HIGHEST))
        S = (eG_[:, -1, None, None] * S
             + jnp.einsum("hik,hiv->hkv", k_end_, U, precision=HIGHEST))
        return S, o

    xs = tuple(a.swapaxes(0, 1) for a in (qc, U0, W, qk, eG, k_end))
    state, o = lax.scan(one, state.astype(F32), xs)
    return o.swapaxes(0, 1).reshape(H, s, dv), state


def gated_delta_step_reference(q, k, v, g, beta, pool, layer, slots):
    """One position a lane against the pool, gathered and scattered. q, k
    (B, heads, dk), v (B, heads, dv), g, beta (B, heads) float32, pool
    (layers, slots + 1, dk, heads x dv) float32, slots (B,) int32 (-1: an
    inactive lane, which writes nothing). Returns (o (B, heads, dv)
    float32, pool)."""
    B, H, dk = q.shape
    dv = v.shape[-1]
    n = pool.shape[1]
    where = jnp.where(slots >= 0, slots, n)         # -1: written nowhere
    S = pool[layer, jnp.clip(slots, 0, n - 1)]
    S = S.reshape(B, dk, H, dv).transpose(0, 2, 1, 3)       # (B, H, dk, dv)
    q, k, v = (a.astype(F32) for a in (q, k, v))
    S = S * jnp.exp(g)[..., None, None]
    mem = jnp.einsum("bhkv,bhk->bhv", S, k, precision=HIGHEST)
    u = (v - mem) * beta[..., None]
    S = S + k[..., None] * u[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", S, q, precision=HIGHEST)
    S = S.transpose(0, 2, 1, 3).reshape(B, dk, H * dv)
    return o, pool.at[layer, where].set(S, mode="drop")


# ------------------------------------------- the triangular inverse
def solve_unit_lower(A, block: int = SOLVE_BLOCK):
    """(I + A)^-1 for A (C, C) float32 strictly lower triangular, from
    matmuls and masks alone (a kernel's body and plain `jax.numpy` both
    run it). With A = L + B, L the part inside diagonal blocks of `block`
    rows: X = (I + L)^-1 by forward substitution, row r of every block in
    one masked matmul; then (I + A)^-1 = (I + X B)^-1 X, and X B is
    nilpotent over the blocks, so its inverse is a finite product."""
    C = A.shape[0]
    block = min(block, C)
    row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = (row == col).astype(F32)
    inside = (row // block) == (col // block)
    L = jnp.where(inside, A, 0.0)
    X = eye
    for r in range(1, block):
        X = X - _dot(jnp.where(row % block == r, L, 0.0), X)
    if block == C:
        return X
    N = _dot(X, jnp.where(inside, 0.0, A))
    inv, power, reach = eye - N, _dot(N, N), 2
    while reach < C // block:           # (I - N)(I + N^2)(I + N^4) ...
        inv, power, reach = (_dot(inv, eye + power), _dot(power, power),
                             2 * reach)
    return _dot(inv, X)


# --------------------------------------------------- the chunk kernel
def _chunk_kernel(len_ref, q_ref, k_ref, v_ref, gc_ref, gr_ref, b_ref,
                  ke_ref, se_ref, o_ref, s_ref, *, chunk: int):
    """Grid (head blocks, chunks), the chunks in order: the state is the
    output block the chunks of a head block share."""
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
        for h in range(heads):
            q, k, v = q_ref[h], k_ref[h], v_ref[h]
            gc, gr, bc = gc_ref[h, 0], gr_ref[h, 0], b_ref[h, 0]
            S = s_ref[h]
            # D_ij = exp(G_i - G_j) at and below the diagonal, else 0
            decay = jnp.exp(jnp.where(row >= col, gc - gr, -jnp.inf))
            kk = _dot(k, k, ((1,), (1,)))
            T = solve_unit_lower(
                jnp.where(row > col, kk * decay, 0.0) * bc)
            eg = jnp.exp(gc)
            kf = k.astype(F32)
            U = (_dot(T, v.astype(F32) * bc)
                 - _dot(_dot(T, kf * (bc * eg)), S))
            qk = _dot(q, k, ((1,), (1,))) * decay
            o_ref[h] = (eg * _dot(q.astype(F32), S)
                        + _dot(qk, U)).astype(o_ref.dtype)
            # to the chunk's end: exp(G_C - G) a key, exp(G_C) the state
            s_ref[h] = (se_ref[h, 0] * S
                        + _dot(kf * ke_ref[h, 0], U, ((0,), (0,))))


def chunk_heads(heads: int) -> int:
    """Heads a grid step of the chunk kernel takes: the largest divisor of
    `heads` up to `CHUNK_HEADS`."""
    return max(n for n in range(1, CHUNK_HEADS + 1) if heads % n == 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _chunk_call(q, k, v, g, beta, true_len, chunk: int, interpret: bool):
    H, s, dk = q.shape
    dv = v.shape[-1]
    n = s // chunk
    if n * chunk != s:
        raise ValueError(f"{s} positions are not whole chunks of {chunk}")
    hb = chunk_heads(H)
    G = jnp.cumsum(g.astype(F32).reshape(H, n, chunk), axis=-1)
    beta = beta.astype(F32).reshape(H, n, chunk, 1)
    # the decays to a chunk's end come as arrays: Mosaic spreads no (1, 1)
    # over a whole tile
    k_end = jnp.exp(G[..., -1:] - G)[..., None]
    s_end = jnp.broadcast_to(jnp.exp(G[..., -1])[..., None, None],
                             (H, n, 1, dv))

    def seq(h, c, len_ref):
        # a chunk past the prompt is not copied in: the last live one stays
        return (h, jnp.minimum(c, (len_ref[0] - 1) // chunk), 0)

    def gate(h, c, len_ref):
        return (*seq(h, c, len_ref), 0)

    call = pl.pallas_call(
        functools.partial(_chunk_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // hb, n),
            in_specs=[pl.BlockSpec((hb, chunk, dk), seq),
                      pl.BlockSpec((hb, chunk, dk), seq),
                      pl.BlockSpec((hb, chunk, dv), seq),
                      pl.BlockSpec((hb, 1, chunk, 1), gate),
                      pl.BlockSpec((hb, 1, 1, chunk), gate),
                      pl.BlockSpec((hb, 1, chunk, 1), gate),
                      pl.BlockSpec((hb, 1, chunk, 1), gate),
                      pl.BlockSpec((hb, 1, 1, dv), gate)],
            out_specs=[pl.BlockSpec((hb, chunk, dv),
                                    lambda h, c, len_ref: (h, c, 0)),
                       pl.BlockSpec((hb, dk, dv),
                                    lambda h, c, len_ref: (h, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct((H, s, dv), q.dtype),
                   jax.ShapeDtypeStruct((H, dk, dv), F32)],
        interpret=interpret,
        name=KERNEL_CHUNK,
    )
    o, state = call(jnp.asarray(true_len, jnp.int32).reshape(1),
                    q, k, v, G[..., None], G[:, :, None, :], beta,
                    k_end, s_end)
    return o, state


def chunk_tiles(dk: int, dv: int, chunk: int, dtype) -> bool:
    """Whether the chunk kernel tiles these shapes: a chunk is whole
    sublane tiles of the activations' dtype and of float32, and a solve
    block divides it."""
    sublanes = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return (chunk % sublanes == 0 and dk % 8 == 0 and dv % 8 == 0
            and chunk % min(SOLVE_BLOCK, chunk) == 0)


def uses_chunk_kernel(dk: int, dv: int, chunk: int, dtype) -> bool:
    """What `gated_delta_prefill` decides: the platform being traced for
    and the shapes."""
    return on_tpu() and chunk_tiles(dk, dv, chunk, dtype)


def _inert_padding(g, beta, true_len):
    """g and beta (heads, s) with the positions from `true_len` on made
    padding: no decay, nothing written."""
    real = (jnp.arange(g.shape[1]) < true_len)[None, :]
    return jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)


def gated_delta_prefill(q, k, v, g, beta, true_len, chunk: int = CHUNK):
    """One padded prompt from a zero state: q, k (heads, s, dk), v (heads,
    s, dv) in the activations' dtype, g, beta (heads, s) float32,
    positions `>= true_len` padding (they leave the state alone). Returns
    (o (heads, s, dv) in q's dtype, zeros past the last live chunk under
    the kernel; the state at `true_len` (heads, dk, dv) float32). The
    kernel on a TPU where the shapes tile, the plain chunked form
    elsewhere."""
    g, beta = _inert_padding(g, beta, true_len)
    if uses_chunk_kernel(q.shape[-1], v.shape[-1], chunk, q.dtype):
        return _chunk_call(q, k, v, g, beta, true_len, chunk, False)
    o, state = gated_delta_chunked(q, k, v, g, beta, chunk=chunk)
    return o.astype(q.dtype), state


def gated_delta_prefill_kernel(q, k, v, g, beta, true_len,
                               chunk: int = CHUNK):
    """Force the Pallas kernel path (interpreter off-TPU) — test hook."""
    g, beta = _inert_padding(g, beta, true_len)
    return _chunk_call(q, k, v, g, beta, true_len, chunk, not on_tpu())


# ---------------------------------------------------- the step kernel
def _step_kernel(layer_ref, slot_ref, qt_ref, kt_ref, v_ref, a_ref, b_ref,
                 s_ref, o_ref, s_out_ref, *, dv: int):
    """Grid (lanes, column blocks of the state): the block (dk, cols) of a
    lane's state, `cols` whole heads side by side. q and k come transposed
    (dk, padded heads) and are spread over their heads' columns by a 0/1
    matrix; v, alpha and beta come spread already (1, cols)."""
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
        spread = (head == lax.broadcasted_iota(
            jnp.int32, (padded, cols), 0)).astype(kt_ref.dtype)
        K = _dot(kt_ref[0], spread)                      # (dk, cols)
        Q = _dot(qt_ref[0], spread)
        Sd = S * a_ref[0]
        mem = jnp.sum(Sd * K, axis=0, keepdims=True)     # (1, cols)
        new = Sd + K * ((v_ref[0] - mem) * b_ref[0])
        s_out_ref[0, 0] = new
        o_ref[0] = jnp.sum(new * Q, axis=0, keepdims=True)


def step_columns(heads: int, dk: int, dv: int) -> int:
    """Columns of the state a grid step of the step kernel takes: whole
    heads, whole 128-lanes, at most `STEP_BLOCK_BYTES` (0: these shapes do
    not tile)."""
    fits = [n * dv for n in range(1, heads + 1)
            if heads % n == 0 and (n * dv) % 128 == 0
            and n * dv * dk * 4 <= STEP_BLOCK_BYTES]
    return max(fits, default=0)


def step_tiles(heads: int, dk: int, dv: int) -> bool:
    return dk % 8 == 0 and step_columns(heads, dk, dv) > 0


def uses_step_kernel(heads: int, dk: int, dv: int) -> bool:
    """What `gated_delta_step` decides: the platform being traced for and
    the shapes."""
    return on_tpu() and step_tiles(heads, dk, dv)


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
                      pl.BlockSpec((1, 1, cols), lane),
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
        v.astype(F32).reshape(B, 1, width), spread(jnp.exp(g)),
        spread(beta), pool)
    return o.reshape(B, H, dv), pool


def gated_delta_step(q, k, v, g, beta, pool, layer, slots):
    """Dispatching entry point of a decode step's recurrence: the kernel
    on a TPU where the shapes tile (the pool updated in place: donate it),
    gather and scatter elsewhere. Shapes as
    `gated_delta_step_reference`."""
    H, dk = q.shape[1:]
    dv = v.shape[-1]
    if uses_step_kernel(H, dk, dv):
        return _step_call(q, k, v, g, beta, pool, layer, slots,
                          step_columns(H, dk, dv), False)
    return gated_delta_step_reference(q, k, v, g, beta, pool, layer, slots)


def gated_delta_step_kernel(q, k, v, g, beta, pool, layer, slots):
    """Force the Pallas kernel path (interpreter off-TPU) — test hook: the
    columns a grid step takes are all of them where the shapes do not
    tile."""
    H, dk = q.shape[1:]
    dv = v.shape[-1]
    return _step_call(q, k, v, g, beta, pool, layer, slots,
                      step_columns(H, dk, dv) or H * dv, not on_tpu())
