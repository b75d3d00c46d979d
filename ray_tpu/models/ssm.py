"""A state-space mixer (a selective scan, `ops.ssd`) as the classes that
have one share it (`SSM`, a `models.paged.Mixer`):
`models.hybrid_ssm_moe.HybridSSMMoE` (a layer is this mixer or another) and
`models.parallel_hybrid.ParallelHybrid` (every layer runs it beside
attention), so that each class's tests and cells guard the other's mixer,
as `models/gqa.py` does for paged attention.

u the mixer's input (normed by its class), H heads of width P in G groups,
a state of N numbers a channel:

    [z | xBC | dt] = (u W_in) * m           (m: `column_scales`, or 1)
    xBC = SiLU(causal depthwise conv of width 4, with bias, over xBC)
    [x | B | C] = xBC               (x: H heads of P; B, C: G groups of N)
    dt = softplus(dt + dt_bias);  a_t = exp(-exp(A_log) dt_t)   (float32)
    h_t = a_t h_{t-1} + dt_t x_t B_t^T;   y_t = h_t C_t + D x_t
    out = (RMSNorm_group(y * SiLU(z))) W_out    (the norm a group's channels;
                                     RMSNorm_group(y) * SiLU(z) where the
                                     class says `norm_before_gate`)

`A_log`, `dt_bias` and `D` are held as offsets from the config's
`a_log_init`, `dt_bias_init` and `d_init`, as a norm's scale is held as an
offset from 1.

What it keeps of a sequence: pools `"state"` `(layers, slots + 1, N, H x
P)` float32 and `"tail"` (the convolution's last `width - 1` inputs,
`(layers, slots + 1, *tail_shape)`), a sequence's at the slot its first
table entry names. A prefill scans a prompt from a zero state
(`ssd_prefill`: the chunk kernel, which stops at the prompt's true length
inside its bucket) and writes the slot whole; a decode step updates the
slots of active lanes in place (`conv_tail_step`, then `ssd_step`; where
its blocks tile the state the step kernel, `ops.ssd.step_columns`).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import regions as R
from ray_tpu.models.paged import (SLOT, Cache, Mixer, Params, Pool, Walk,
                                  write_slot)
from ray_tpu.ops import ssd as _ssd
from ray_tpu.ops.conv import causal_conv, conv_tail_step, tail_shape
from ray_tpu.ops.norms import rms_norm_reference


class SSMDims:
    """The mixer's widths of a config that has `ssm_heads`, `ssm_head_dim`,
    `ssm_groups` and `ssm_state`."""

    @property
    def ssm_inner(self) -> int:             # the mixer's width, H x P
        return self.ssm_heads * self.ssm_head_dim

    @property
    def bc_dim(self) -> int:                # B's (and C's) width, G x N
        return self.ssm_groups * self.ssm_state

    @property
    def conv_channels(self) -> int:
        return self.ssm_inner + 2 * self.bc_dim


class SSM(Mixer):
    """The mixer of `config` (an `SSMDims` with `d_model`, `conv_width`,
    `chunk`, `a_log_init`, `dt_bias_init`, `d_init`, `norm_eps`) over a
    layer's leaves (`shapes`). `in_scale` multiplies the mixer's input,
    `column_scales` (five scalars over W_in's columns [z | x | B | C | dt])
    its product; `norm_before_gate` is the gated norm's order: the norm of
    y, then the gate."""

    def __init__(self, config, in_scale: Optional[float] = None,
                 column_scales: Optional[Tuple[float, ...]] = None,
                 norm_before_gate: bool = False):
        c = self.config = config
        self.in_scale, self.column_scales = in_scale, column_scales
        self.norm_before_gate = norm_before_gate
        self.chunk = c.chunk
        self.pools = (
            Pool("state", SLOT, (c.ssm_state, c.ssm_inner), jnp.float32),
            Pool("tail", SLOT, tail_shape(c.conv_width, c.conv_channels)))

    def shapes(self, std: float, out_std: float) -> Dict[
            str, Tuple[tuple, float]]:
        """A mixer's leaves as `(shape, init std)`; zeros are the gate
        norm's scale w (the layer multiplies by 1 + w), the offsets
        `a_log`, `dt_bias`, `d`, and the convolution's bias."""
        c = self.config
        H = c.ssm_heads
        return {"w_in": ((c.d_model, c.ssm_inner + c.conv_channels + H),
                         std),
                "conv": ((c.conv_width, c.conv_channels), std),
                "conv_bias": ((c.conv_channels,), 0.0),
                "a_log": ((H,), 0.0), "dt_bias": ((H,), 0.0),
                "d": ((H,), 0.0), "gate_norm": ((c.ssm_inner,), 0.0),
                "w_out": ((c.ssm_inner, c.d_model), out_std)}

    def decode_kernel(self, page_size: int, dtype) -> str:
        """The name of a decode step's recurrence."""
        c = self.config
        return (_ssd.KERNEL_STEP if _ssd.uses_step_kernel(
            c.ssm_inner, c.ssm_inner // c.ssm_groups, c.ssm_state)
            else "ssd_gather")

    # --------------------------------------------------------- pieces
    @R.region(R.MIXER_IN)
    def _scaled(self, h):
        return h if self.in_scale is None else h * self.in_scale

    @R.region(R.MIXER_IN)
    def _project(self, layer: Params, u):
        """u (n, e) -> (z (n, H x P), xBC (n, channels) before the
        convolution, dt (n, H) before the softplus)."""
        c = self.config
        proj = u @ layer["w_in"].astype(c.activation_dtype)
        if self.column_scales is not None:
            widths = (c.ssm_inner, c.ssm_inner, c.bc_dim, c.bc_dim,
                      c.ssm_heads)
            proj = proj * jnp.concatenate([
                jnp.full((n,), m, c.activation_dtype)
                for n, m in zip(widths, self.column_scales)])
        return jnp.split(proj, [c.ssm_inner, c.ssm_inner + c.conv_channels],
                         axis=-1)

    @R.region(R.MIXER_IN)
    def _inputs(self, layer: Params, mixed, dt):
        """What the scan takes: x (n, H x P), B, C (n, G x N) of the
        convolved channels `mixed`, dt (n, H) and A (H,) float32."""
        c = self.config
        f32 = jnp.float32           # the offsets are added in float32
        x, Bm, Cm = jnp.split(mixed, [c.ssm_inner, c.ssm_inner + c.bc_dim],
                              axis=-1)
        dt = jax.nn.softplus(dt.astype(f32) + c.dt_bias_init
                             + layer["dt_bias"].astype(f32))
        return x, Bm, Cm, dt, jnp.exp(c.a_log_init
                                      + layer["a_log"].astype(f32))

    @R.region(R.MIXER_OUT)
    def _out(self, layer: Params, y, x, z):
        """The scan's y (n, H x P): the skip `D x` added, gated by SiLU(z)
        and normed a group's channels (in the class's order), through
        W_out; float32 up to the matmul."""
        c = self.config
        f32 = jnp.float32
        n, G = y.shape[0], c.ssm_groups
        D = jnp.repeat(c.d_init + layer["d"].astype(f32), c.ssm_head_dim)
        y = y.astype(f32) + D * x.astype(f32)
        gate = jax.nn.silu(z.astype(f32))

        def normed(a):
            return rms_norm_reference(
                a.reshape(n, G, -1), layer["gate_norm"].reshape(G, -1),
                c.norm_eps).reshape(n, -1)

        y = normed(y) * gate if self.norm_before_gate else normed(
            y * gate)
        ad = c.activation_dtype
        return y.astype(ad) @ layer["w_out"].astype(ad)

    def _seq(self, layer: Params, u, true_len=None):
        """The mixer over one sequence u (s, e). With a `true_len` (a
        prefill's padded bucket) through `ssd_prefill`, the kernel where
        there is one; without, through the plain chunked form, which JAX
        differentiates. Returns (the output after W_out (s, e), the state
        at the sequence's end (N, H x P) float32, the convolution's
        tail)."""
        c = self.config
        s = u.shape[0]
        z, xbc, dt = self._project(layer, u)
        with R.region(R.MIXER_IN):
            mixed, tail = causal_conv(xbc, layer["conv"], true_len,
                                      layer["conv_bias"])
            x, Bm, Cm, dt, A = self._inputs(layer, mixed, dt)
            pad = -s % c.chunk              # whole chunks; padding is inert
            xp, Bp, Cp, dtp = (jnp.pad(a, ((0, pad), (0, 0)))
                               for a in (x, Bm, Cm, dt))
        with R.region(R.MIXER_CORE):
            if true_len is None:
                y, state = _ssd.ssd_chunked(xp, Bp, Cp, dtp, A,
                                            c.ssm_groups, chunk=c.chunk)
            else:
                y, state = _ssd.ssd_prefill(xp, Bp, Cp, dtp, A, true_len,
                                            c.ssm_groups, c.chunk)
            y = y[:s]
        return self._out(layer, y, x, z), state, tail

    def _step(self, layer: Params, u, pools: Cache, li: int, slot):
        """One decode position a lane, u (B, e): the tails and states at
        row `li` of the pools advanced in place at the lanes' `slot`s (-1:
        left alone). Returns (the output after W_out (B, e), the two
        pools)."""
        c = self.config
        z, xbc, dt = self._project(layer, u)
        with R.region(R.MIXER_IN):
            conv, tail = conv_tail_step(
                xbc, layer["conv"], pools["tail"], li, slot,
                layer["conv_bias"])
        xs, Bm, Cm, dt, A = self._inputs(layer, conv, dt)
        with R.region(R.MIXER_CORE):
            y, state = _ssd.ssd_step(xs, Bm, Cm, dt, A, pools["state"], li,
                                     slot, c.ssm_groups)
        return self._out(layer, y, xs, z), {"tail": tail, "state": state}

    # ------------------------------------------------------- forwards
    def hidden(self, layer: Params, h, at: Walk):
        return jax.vmap(lambda seq: self._seq(layer, seq)[0])(
            self._scaled(h))

    def prefill(self, layer: Params, h, pools: Cache, li: int, at: Walk):
        out, state, tail = self._seq(layer, self._scaled(h), at.true_len)
        return out, write_slot(pools, li, at.slot, state, tail)

    def decode_step(self, layer: Params, h, pools: Cache, li: int,
                    at: Walk):
        return self._step(layer, self._scaled(h), pools, li, at.slot)
