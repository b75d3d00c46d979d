"""The selective scan of a state-space layer (the state-space duality
form, Mamba-2's): its recurrence as two Pallas TPU kernels and their plain
`jax.numpy` twins.

A head of width P keeps a state `h` (P x N, float32) a sequence, whatever
the sequence's length. Heads come in groups that share `B` and `C` (N
numbers a group and position); with `dt_t > 0` a head's step and `A > 0`
its rate, both float32:

    a_t = exp(-A dt_t)                      (a head's decay, in (0, 1))
    h_t = a_t h_{t-1} + dt_t x_t B_t^T
    y_t = h_t C_t

(the skip `D x_t`, the gate and the norm around it are the model's).
`ssd_recurrence` writes that position by position (the tests' ground
truth). Prefill computes the same by chunks of `chunk` positions
(`ssd_chunked`, plain and differentiable: a model's `apply` and the path
off the TPU; `ssd_chunk_fwd`, the kernel). With `L` the running sum of
`-A dt` inside a chunk and `h_0` the state the chunk starts from:

    M_ij = (C_i . B_j) exp(L_i - L_j) dt_j      for j <= i, else 0
    Y    = M X + diag(exp(L)) C h_0^T           (inside, and from before)
    h_C  = exp(L_C) h_0 + (diag(dt exp(L_C - L)) X)^T B

There is no solve (the delta rule's chunk has one): `C B^T` is one product
a group and chunk, shared by the group's heads; each head's decays mask it.
`exp(L_i - L_j)` is taken of the difference, never as a quotient of two
exponentials: a chunk's decays may underflow float32. A padded position has
`dt = 0`: it neither decays nor writes, so the state after a padded bucket
is the state at the prompt's true length, and the kernel skips the chunks
past it.

**Layouts.** Activations keep the layout the projections give them: x `(s,
H x P)`, heads side by side; B, C `(s, G x N)`. The state is `(N, H x P)`:
a state's columns are the heads' channels, so that what varies by channel
(x, a head's decay) is a row and a group's B and C are columns, the
products with the state (`C h_0^T`, `X^T B`) are one matmul a group, and a
pool row is whole 128-lanes. The pool is `(layers, slots + 1, N, H x P)`
float32, a sequence's state at the slot its page table names, the last slot
nobody's (`ops.gated_delta` says the same of its pool).

Decode advances one position a lane (`ssd_step`, the kernel;
`ssd_step_reference`): the pool is aliased in and out, only the slots of
active lanes are written, and the update is elementwise float32 under the
copies: the kernel is bound by the bytes of the state, read and written
once. A grid step takes a block of the state's columns (`step_columns`):
whole groups side by side where a group fits `STEP_BLOCK_BYTES`, part of
one group where it does not (16 heads of 128 over a state of 256 are 2
MiB); a column's group is read off its place in the width, so a block
needs no more of the groups than whole 128-lanes.
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
KERNEL_CHUNK = "ssd_chunk_fwd"
KERNEL_STEP = "ssd_step"

CHUNK = 128             # positions a chunk: the family's `chunk_size`
# bytes of state a grid step of the step kernel holds (in, out, and the
# temporaries of the update are each this much)
STEP_BLOCK_BYTES = 1 << 20
LANES = 128


def _dims(x, Bm, heads: int, groups: int):
    """(P, N, heads a group) of x (..., heads x P) and B (..., groups x
    N)."""
    if heads % groups or x.shape[-1] % heads or Bm.shape[-1] % groups:
        raise ValueError(f"{heads} heads in {groups} groups over widths "
                         f"{x.shape[-1]}, {Bm.shape[-1]}")
    return x.shape[-1] // heads, Bm.shape[-1] // groups, heads // groups


# ------------------------------------------------------- plain twins
def ssd_recurrence(x, Bm, Cm, dt, A, groups: int, state=None):
    """The recurrence position by position. x (s, H x P), Bm, Cm (s, G x
    N), dt (s, H) float32, A (H,) float32, state (N, H x P) float32 or
    None for zeros. Returns (y (s, H x P) float32, the last state)."""
    H = dt.shape[-1]
    P, N, hpg = _dims(x, Bm, H, groups)
    if state is None:
        state = jnp.zeros((N, H * P), F32)

    def step(S, inp):
        xt, bt, ct, dtt = inp
        a = jnp.exp(-A * dtt)                                   # (H,)
        bh = jnp.repeat(bt.reshape(groups, N), hpg, axis=0)     # (H, N)
        ch = jnp.repeat(ct.reshape(groups, N), hpg, axis=0)
        S = S.reshape(N, H, P) * a[None, :, None] + (
            bh.T[:, :, None] * (dtt[:, None] * xt.reshape(H, P))[None])
        y = jnp.einsum("nhp,hn->hp", S, ch, precision=HIGHEST)
        return S.reshape(N, H * P), y.reshape(H * P)

    A = A.astype(F32)
    xs = tuple(a.astype(F32) for a in (x, Bm, Cm, dt))
    state, y = lax.scan(step, state.astype(F32), xs)
    return y, state


def ssd_chunked(x, Bm, Cm, dt, A, groups: int, state=None,
                chunk: int = CHUNK):
    """The same by chunks, in plain float32 `jax.numpy` (differentiable):
    shapes as `ssd_recurrence`, s a multiple of `chunk`."""
    s, H = dt.shape
    P, N, hpg = _dims(x, Bm, H, groups)
    n = s // chunk
    if n * chunk != s:
        raise ValueError(f"{s} positions are not whole chunks of {chunk}")
    if state is None:
        state = jnp.zeros((N, H * P), F32)
    dt = dt.astype(F32)
    xc = x.astype(F32).reshape(n, chunk, groups, hpg, P)
    bc = Bm.astype(F32).reshape(n, chunk, groups, N)
    cc = Cm.astype(F32).reshape(n, chunk, groups, N)
    dc = dt.reshape(n, chunk, groups, hpg)
    L = jnp.cumsum((-A.astype(F32) * dt).reshape(n, chunk, groups, hpg),
                   axis=1)                          # inside each chunk
    at_or_below = jnp.tri(chunk, dtype=bool)
    decay = jnp.exp(jnp.where(
        at_or_below[None, :, :, None, None],
        L[:, :, None] - L[:, None, :], -jnp.inf))       # (n, i, j, G, hpg)
    cb = jnp.einsum("cign,cjgn->cijg", cc, bc, precision=HIGHEST)
    M = cb[..., None] * decay * dc[:, None]             # dt_j
    y_in = jnp.einsum("cijgh,cjghp->cighp", M, xc, precision=HIGHEST)
    end = L[:, -1:]                                     # (n, 1, G, hpg)
    xw = xc * (dc * jnp.exp(end - L))[..., None]
    eL = jnp.exp(L)

    def one(S, inp):
        c_, y_in_, xw_, b_, eL_, end_ = inp
        Sg = S.reshape(N, groups, hpg, P)
        y = y_in_ + eL_[..., None] * jnp.einsum(
            "ign,nghp->ighp", c_, Sg, precision=HIGHEST)
        Sg = jnp.exp(end_[0])[None, :, :, None] * Sg + jnp.einsum(
            "jgn,jghp->nghp", b_, xw_, precision=HIGHEST)
        return Sg.reshape(N, H * P), y.reshape(chunk, H * P)

    state, y = lax.scan(one, state.astype(F32), (cc, y_in, xw, bc, eL, end))
    return y.reshape(s, H * P), state


def ssd_step_reference(x, Bm, Cm, dt, A, pool, layer, slots, groups: int):
    """One position a lane against the pool, gathered and scattered. x (B,
    H x P), Bm, Cm (B, G x N), dt (B, H) float32, A (H,), pool (layers,
    slots + 1, N, H x P) float32, slots (B,) int32 (-1: an inactive lane,
    which writes nothing). Returns (y (B, H x P) float32, pool)."""
    nb, H = dt.shape
    P, N, hpg = _dims(x, Bm, H, groups)
    n = pool.shape[1]
    where = jnp.where(slots >= 0, slots, n)         # -1: written nowhere
    S = pool[layer, jnp.clip(slots, 0, n - 1)].reshape(nb, N, H, P)
    a = jnp.exp(-A.astype(F32) * dt.astype(F32))                # (B, H)
    bh = jnp.repeat(Bm.astype(F32).reshape(nb, groups, N), hpg, axis=1)
    ch = jnp.repeat(Cm.astype(F32).reshape(nb, groups, N), hpg, axis=1)
    u = dt.astype(F32)[..., None] * x.astype(F32).reshape(nb, H, P)
    S = S * a[:, None, :, None] + bh.transpose(0, 2, 1)[..., None] * u[
        :, None]
    y = jnp.einsum("bnhp,bhn->bhp", S, ch, precision=HIGHEST)
    return (y.reshape(nb, H * P),
            pool.at[layer, where].set(S.reshape(nb, N, H * P), mode="drop"))


# --------------------------------------------------- the chunk kernel
def block_columns(head_dim: int, group_cols: int) -> int:
    """Columns of a group the chunk kernel multiplies at a time: whole
    heads, a whole 128-lanes where the heads are narrower (each head's
    masked product then fills the lanes its neighbours leave), never more
    than the group has."""
    return min(group_cols, max(head_dim, LANES))


def _chunk_kernel(len_ref, x_ref, b_ref, c_ref, lc_ref, lr_ref, dtc_ref,
                  dtr_ref, o_ref, s_ref, *, chunk: int, head_dim: int):
    """Grid (groups, chunks), the chunks in order: the state is the output
    block the chunks of a group share."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    live = c * chunk < len_ref[0]

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _():
        hpg, cols = lc_ref.shape[-1], x_ref.shape[-1]
        bc = block_columns(head_dim, cols)
        x, Bm, Cm = x_ref[...], b_ref[...], c_ref[...]
        Lc, Lr = lc_ref[0], lr_ref[0]           # (chunk, hpg), (hpg, chunk)
        dtc, dtr = dtc_ref[0], dtr_ref[0]
        S = s_ref[...]                              # (N, cols)
        # a number a head, spread over its head's columns by a 0/1 matrix
        spread = (lax.broadcasted_iota(jnp.int32, (hpg, cols), 1) // head_dim
                  == lax.broadcasted_iota(jnp.int32, (hpg, cols), 0)
                  ).astype(F32)
        end = Lc[chunk - 1:chunk]                   # (1, hpg): L_C
        from_before = _dot(Cm, S) * _dot(jnp.exp(Lc), spread)
        xw = x.astype(F32) * _dot(dtc * jnp.exp(end - Lc), spread)
        s_ref[...] = (_dot(jnp.exp(end), spread) * S
                      + _dot(Bm.astype(F32), xw, ((0,), (0,))))
        cb = _dot(Cm, Bm, ((1,), (1,)))             # (chunk, chunk)
        row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        head_of = lax.broadcasted_iota(jnp.int32, (1, bc), 1) // head_dim
        for j in range(cols // bc):
            at = slice(j * bc, (j + 1) * bc)
            xb = x[:, at].astype(F32)
            y = from_before[:, at]
            for i in range(bc // head_dim):
                h = j * (bc // head_dim) + i
                # exp of the difference, masked before it is taken
                M = cb * dtr[h:h + 1, :] * jnp.exp(jnp.where(
                    row >= col, Lc[:, h:h + 1] - Lr[h:h + 1, :], -jnp.inf))
                y = y + _dot(M, jnp.where(head_of == i, xb, 0.0))
            o_ref[:, at] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "groups", "chunk",
                                             "interpret"))
def _chunk_call(x, Bm, Cm, dt, A, true_len, heads: int, groups: int,
                chunk: int, interpret: bool):
    s = x.shape[0]
    P, N, hpg = _dims(x, Bm, heads, groups)
    n = s // chunk
    if n * chunk != s:
        raise ValueError(f"{s} positions are not whole chunks of {chunk}")
    cols = hpg * P
    dt = dt.astype(F32)
    L = jnp.cumsum((-A.astype(F32) * dt).reshape(n, chunk, heads), axis=1)

    def by_group(a):            # (s, H) -> (G, s, hpg) and its transpose
        a = a.reshape(s, groups, hpg).transpose(1, 0, 2)
        return a, a.transpose(0, 2, 1)

    Lc, Lr = by_group(L)
    dtc, dtr = by_group(dt)

    def at(c, len_ref):
        # a chunk past the prompt is not copied in: the last live one stays
        return jnp.minimum(c, (len_ref[0] - 1) // chunk)

    seq = lambda g, c, len_ref: (at(c, len_ref), g)             # noqa: E731
    down = lambda g, c, len_ref: (g, at(c, len_ref), 0)         # noqa: E731
    across = lambda g, c, len_ref: (g, 0, at(c, len_ref))       # noqa: E731
    call = pl.pallas_call(
        functools.partial(_chunk_kernel, chunk=chunk, head_dim=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(groups, n),
            in_specs=[pl.BlockSpec((chunk, cols), seq),
                      pl.BlockSpec((chunk, N), seq),
                      pl.BlockSpec((chunk, N), seq),
                      pl.BlockSpec((1, chunk, hpg), down),
                      pl.BlockSpec((1, hpg, chunk), across),
                      pl.BlockSpec((1, chunk, hpg), down),
                      pl.BlockSpec((1, hpg, chunk), across)],
            out_specs=[pl.BlockSpec((chunk, cols),
                                    lambda g, c, len_ref: (c, g)),
                       pl.BlockSpec((N, cols),
                                    lambda g, c, len_ref: (0, g))]),
        out_shape=[jax.ShapeDtypeStruct((s, heads * P), x.dtype),
                   jax.ShapeDtypeStruct((N, heads * P), F32)],
        interpret=interpret,
        name=KERNEL_CHUNK,
    )
    y, state = call(jnp.asarray(true_len, jnp.int32).reshape(1),
                    x, Bm, Cm, Lc, Lr, dtc, dtr)
    return y, state


def chunk_tiles(head_dim: int, state: int, heads_per_group: int, chunk: int,
                groups: int) -> bool:
    """Whether the chunk kernel tiles these shapes: a chunk (a row of the
    transposed decays), a group's columns and its B and C are whole
    128-lanes, and the heads fill a block of columns."""
    cols = heads_per_group * head_dim
    bc = block_columns(head_dim, cols)
    return (chunk % LANES == 0 and cols % LANES == 0
            and (state % LANES == 0 or groups == 1)
            and bc % head_dim == 0 and cols % bc == 0)


def uses_chunk_kernel(head_dim: int, state: int, heads_per_group: int,
                      chunk: int, groups: int) -> bool:
    """What `ssd_prefill` decides: the platform being traced for and the
    shapes."""
    return on_tpu() and chunk_tiles(head_dim, state, heads_per_group, chunk,
                                    groups)


def _inert_padding(dt, true_len):
    """dt (s, H) with the positions from `true_len` on made padding: no
    decay, nothing written."""
    return jnp.where((jnp.arange(dt.shape[0]) < true_len)[:, None], dt, 0.0)


def ssd_prefill(x, Bm, Cm, dt, A, true_len, groups: int,
                chunk: int = CHUNK):
    """One padded prompt from a zero state: x (s, H x P), Bm, Cm (s, G x
    N) in the activations' dtype, dt (s, H) float32, A (H,) float32,
    positions `>= true_len` padding (they leave the state alone). Returns
    (y (s, H x P) in x's dtype, zeros past the last live chunk under the
    kernel; the state at `true_len` (N, H x P) float32). The kernel on a
    TPU where the shapes tile, the plain chunked form elsewhere."""
    H = dt.shape[-1]
    P, N, hpg = _dims(x, Bm, H, groups)
    dt = _inert_padding(dt, true_len)
    if uses_chunk_kernel(P, N, hpg, chunk, groups):
        return _chunk_call(x, Bm, Cm, dt, A, true_len, H, groups, chunk,
                           False)
    y, state = ssd_chunked(x, Bm, Cm, dt, A, groups, chunk=chunk)
    return y.astype(x.dtype), state


def ssd_prefill_kernel(x, Bm, Cm, dt, A, true_len, groups: int,
                       chunk: int = CHUNK):
    """Force the Pallas kernel path (interpreter off-TPU) — test hook."""
    return _chunk_call(x, Bm, Cm, _inert_padding(dt, true_len), A, true_len,
                       dt.shape[-1], groups, chunk, not on_tpu())


# ---------------------------------------------------- the step kernel
def _step_kernel(layer_ref, slot_ref, bt_ref, ct_ref, u_ref, a_ref, s_ref,
                 o_ref, s_out_ref, *, group_cols: int):
    """Grid (lanes, column blocks of the state): the block (N, cols) of a
    lane's state, `cols` whole groups side by side or part of one. B and
    C come transposed (N, padded groups) and are spread over the block's
    columns by a 0/1 matrix that names each column's group by its place
    in the width (`j * cols + column`, over `group_cols`); `u = dt x` and
    the decay come spread already (1, cols)."""
    del layer_ref
    b, j = pl.program_id(0), pl.program_id(1)
    cols = s_ref.shape[-1]
    padded = bt_ref.shape[-1]
    S = s_ref[0, 0]

    @pl.when(slot_ref[b] < 0)
    def _():                    # nobody's slot: as it was
        s_out_ref[0, 0] = S
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(slot_ref[b] >= 0)
    def _():
        group = (j * cols + lax.broadcasted_iota(
            jnp.int32, (padded, cols), 1)) // group_cols
        spread = (group == lax.broadcasted_iota(
            jnp.int32, (padded, cols), 0)).astype(bt_ref.dtype)
        new = S * a_ref[0] + _dot(bt_ref[0], spread) * u_ref[0]
        s_out_ref[0, 0] = new
        o_ref[0] = jnp.sum(new * _dot(ct_ref[0], spread), axis=0,
                           keepdims=True)


def step_columns(width: int, group_cols: int, state: int) -> int:
    """Columns of the state a grid step of the step kernel takes: whole
    128-lanes, at most `STEP_BLOCK_BYTES`; whole groups side by side where
    a group fits, else the largest part of one group that divides it (0:
    these shapes do not tile)."""
    groups = width // group_cols
    whole = [n * group_cols for n in range(1, groups + 1) if groups % n == 0]
    parts = [group_cols // n for n in range(2, group_cols // LANES + 1)
             if group_cols % n == 0]
    fits = [cols for cols in whole + parts if cols % LANES == 0
            and cols * state * 4 <= STEP_BLOCK_BYTES]
    return max(fits, default=0)


def step_tiles(width: int, group_cols: int, state: int) -> bool:
    return state % 8 == 0 and step_columns(width, group_cols, state) > 0


def uses_step_kernel(width: int, group_cols: int, state: int) -> bool:
    """What `ssd_step` decides: the platform being traced for and the
    shapes."""
    return on_tpu() and step_tiles(width, group_cols, state)


# jitted for the reason `paged_attention._paged_decode_call` is: traced
# once a program, the layer an argument
@functools.partial(jax.jit, static_argnames=("groups", "cols", "interpret"))
def _step_call(x, Bm, Cm, dt, A, pool, layer, slots, groups: int, cols: int,
               interpret: bool):
    nb, H = dt.shape
    P, N, hpg = _dims(x, Bm, H, groups)
    width = H * P
    padded = -(-groups // 16) * 16      # whole sublanes of a 0/1 matrix
    trash = pool.shape[1] - 1
    dt = dt.astype(F32)

    def transposed(a):                                  # (B, N, padded)
        a = a.reshape(nb, groups, N)
        return jnp.pad(a, ((0, 0), (0, padded - groups), (0, 0))).transpose(
            0, 2, 1)

    def spread(a):                                      # a number a head
        return jnp.repeat(a, P, axis=-1)[:, None, :]

    def lane(b, j, layer_ref, slot_ref):
        return (b, 0, j)

    def whole(b, j, layer_ref, slot_ref):
        return (b, 0, 0)

    def state(b, j, layer_ref, slot_ref):
        slot = slot_ref[b]
        return (layer_ref[0], jnp.where(slot < 0, trash, slot), 0, j)

    call = pl.pallas_call(
        functools.partial(_step_kernel, group_cols=hpg * P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb, width // cols),
            in_specs=[pl.BlockSpec((1, N, padded), whole),
                      pl.BlockSpec((1, N, padded), whole),
                      pl.BlockSpec((1, 1, cols), lane),
                      pl.BlockSpec((1, 1, cols), lane),
                      pl.BlockSpec((1, 1, N, cols), state)],
            out_specs=[pl.BlockSpec((1, 1, cols), lane),
                       pl.BlockSpec((1, 1, N, cols), state)]),
        out_shape=[jax.ShapeDtypeStruct((nb, 1, width), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool (after the two scalar arguments) is the second output
        input_output_aliases={6: 1},
        interpret=interpret,
        name=KERNEL_STEP,
    )
    y, pool = call(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.where(slots < trash, slots, -1).astype(jnp.int32),
        transposed(Bm), transposed(Cm),
        spread(dt) * x.astype(F32)[:, None, :],
        spread(jnp.exp(-A.astype(F32) * dt)), pool)
    return y.reshape(nb, width), pool


def ssd_step(x, Bm, Cm, dt, A, pool, layer, slots, groups: int):
    """Dispatching entry point of a decode step's recurrence: the kernel
    on a TPU where the shapes tile (the pool updated in place: donate it),
    gather and scatter elsewhere. Shapes as `ssd_step_reference`."""
    H = dt.shape[-1]
    P, N, hpg = _dims(x, Bm, H, groups)
    if uses_step_kernel(H * P, hpg * P, N):
        return _step_call(x, Bm, Cm, dt, A, pool, layer, slots, groups,
                          step_columns(H * P, hpg * P, N), False)
    return ssd_step_reference(x, Bm, Cm, dt, A, pool, layer, slots, groups)


def ssd_step_kernel(x, Bm, Cm, dt, A, pool, layer, slots, groups: int):
    """Force the Pallas kernel path (interpreter off-TPU) — test hook: the
    columns a grid step takes are all of them where the shapes do not
    tile."""
    H = dt.shape[-1]
    P, N, hpg = _dims(x, Bm, H, groups)
    return _step_call(x, Bm, Cm, dt, A, pool, layer, slots, groups,
                      step_columns(H * P, hpg * P, N) or H * P,
                      not on_tpu())
