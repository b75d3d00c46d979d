"""The causal depthwise convolution of the recurrent mixers and of the gated
short convolution, plain `jax.numpy`: `causal_conv` over a prompt,
`conv_tail_step` a decode step. The inputs a sequence's convolution
continues from live in a pool `(layers, slots + 1, *tail_shape)`, a
sequence's at the slot its page table names (beside a recurrence's state,
where there is one), the last slot nobody's.

The convolution ends in SiLU where it is a recurrence's way in
(`ops/gated_delta.py`, `ops/kda.py`, `ops/ssd.py`: the default) and is
linear (`activate=False`) where it is the mixer itself, between two gates
the caller multiplies by (`models/gated_conv_moe.py`): its tail pool is
then all a layer keeps of a sequence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.exact import F32

# a convolution's input in a tail pool: rows of a vector's lanes, and whole
# tiles of them (a bfloat16 tile's sublanes; two of a float32's)
TAIL_LANES, TAIL_ROWS = 128, 16


def causal_conv(x, w, true_len=None, bias=None, activate: bool = True):
    """Depthwise causal convolution along the sequence, then SiLU: x (s,
    channels), w (width, channels), `y_t = silu(sum_i w_i x_{t - width + 1
    + i} + bias)` with zeros before the sequence (`bias` (channels,) or
    None: none); the sum itself, linear, without `activate`. Returns (y in
    x's dtype, the last `width - 1` inputs before `true_len` (the
    sequence's end if None): what a decode step continues from)."""
    s, width = x.shape[0], w.shape[0]
    xf = jnp.pad(x.astype(F32), ((width - 1, 0), (0, 0)))
    y = sum(w[i].astype(F32) * xf[i:i + s] for i in range(width))
    if bias is not None:
        y = y + bias.astype(F32)
    end = s if true_len is None else true_len
    # padded row `end + j` is input `end - (width - 1) + j`
    tail = lax.dynamic_slice_in_dim(xf, end, width - 1, axis=0)
    if activate:
        y = jax.nn.silu(y)
    return y.astype(x.dtype), tail.astype(x.dtype)


def conv_step(x, tail, w, bias=None, activate: bool = True):
    """One position of `causal_conv` a lane: x (B, channels), tail (B,
    width - 1, channels) the inputs before it. Returns (y, the new
    tail)."""
    window = jnp.concatenate([tail, x[:, None]], axis=1)
    y = jnp.sum(window.astype(F32) * w.astype(F32)[None], axis=1)
    if bias is not None:
        y = y + bias.astype(F32)
    if activate:
        y = jax.nn.silu(y)
    return y.astype(x.dtype), window[:, 1:]


def tail_shape(width: int, channels: int) -> tuple:
    """A sequence's entry in a tail pool `(layers, slots + 1,
    *tail_shape)`: its last `width - 1` inputs, each folded into rows of
    128 lanes, the rows padded with zeros to whole tiles of 16: `(width -
    1, rows, 128)`. A slot is then whole tiles, contiguous in the layout
    XLA gives the pool by default, and a step's scatter writes a lane's
    rows as they lie. XLA lays a dimension that tiles would pad behind one
    they pad less: of `(slots + 1, (width - 1) x channels)` the slots, so
    that a slot is one sublane of every tile and each lane's write merges
    16 times its bytes, in a loop a lane (2.6 us each on a v5e); the 3 of
    `(width - 1, channels)`, or 90 rows beside 128 slots, the same way
    (PERF.md section 6, PR 51). Channels that are not whole lanes stay one
    row, `(width - 1, 1, channels)`."""
    if channels % TAIL_LANES:
        return (width - 1, 1, channels)
    rows = -(-channels // (TAIL_LANES * TAIL_ROWS)) * TAIL_ROWS
    return (width - 1, rows, TAIL_LANES)


def fold_tail(a, shape):
    """a (..., channels) folded into rows and lanes `(..., *shape[-2:])` of
    a tail pool of `shape`, zeros past its channels."""
    rows, lanes = shape[-2:]
    pad = rows * lanes - a.shape[-1]
    if pad:
        a = jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, pad),))
    return a.reshape(*a.shape[:-1], rows, lanes)


def _unfold_tail(a, channels: int):
    """`fold_tail` undone: (..., rows, lanes) -> (..., channels)."""
    return a.reshape(*a.shape[:-2], -1)[..., :channels]


def conv_tail_step(x, w, pool, layer, slots, bias=None,
                   activate: bool = True):
    """One position of `causal_conv` a lane against the pool of tails:
    x (B, channels) the new inputs, w (width, channels), pool (layers,
    slots + 1, *tail_shape), slots (B,) int32 (-1: an inactive lane, which
    reads nobody's rows and writes nothing). Returns (y (B, channels) in
    x's dtype, the pool with the active lanes' slots shifted by x)."""
    n = pool.shape[1] - 1
    tail = pool[layer, jnp.where(slots >= 0, slots, n)]
    y, tail = conv_step(x, _unfold_tail(tail, x.shape[1]), w, bias,
                        activate)
    where = jnp.where(slots >= 0, slots, n + 1)         # -1: written nowhere
    return y, pool.at[layer, where].set(
        fold_tail(tail, pool.shape).astype(pool.dtype), mode="drop")
