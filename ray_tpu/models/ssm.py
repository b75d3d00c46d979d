"""A state-space mixer (a selective scan, `ops.ssd`) as the classes that
have one share it: `models.hybrid_ssm_moe.HybridSSMMoE` (a layer is this
mixer or another) and `models.parallel_hybrid.ParallelHybrid` (every layer
runs it beside attention), so that each class's tests and cells guard the
other's mixer, as `models/gqa.py` does for paged attention.

u the mixer's input (normed by its class), H heads of width P in G groups,
a state of N numbers a channel:

    [z | xBC | dt] = (u W_in) * m           (m: `ssm_column_scale`, or 1)
    xBC = SiLU(causal depthwise conv of width 4, with bias, over xBC)
    [x | B | C] = xBC               (x: H heads of P; B, C: G groups of N)
    dt = softplus(dt + dt_bias);  a_t = exp(-exp(A_log) dt_t)   (float32)
    h_t = a_t h_{t-1} + dt_t x_t B_t^T;   y_t = h_t C_t + D x_t
    out = (RMSNorm_group(y * SiLU(z))) W_out    (the norm a group's channels;
                                     RMSNorm_group(y) * SiLU(z) where the
                                     class says `ssm_norm_before_gate`)

`A_log`, `dt_bias` and `D` are held as offsets from the config's
`a_log_init`, `dt_bias_init` and `d_init`, as a norm's scale is held as an
offset from 1.

What it keeps of a sequence (`paged.StateSlots`): pools `"state"` `(layers,
slots + 1, N, H x P)` float32 and `"tail"` (the convolution's last `width -
1` inputs, `(layers, slots + 1, *tail_shape)`), a sequence's at the slot its
first table entry names. A prefill scans a prompt from a zero state
(`ssd_prefill`: the chunk kernel, which stops at the prompt's true length
inside its bucket) and the class writes the slot whole; a decode step
updates the slots of active lanes in place (`conv_tail_step`, then
`ssd_step`).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import regions as R
from ray_tpu.models.paged import Cache, Params
from ray_tpu.ops import gated_delta as _gd
from ray_tpu.ops import ssd as _ssd
from ray_tpu.ops.gated_delta import causal_conv
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


class SSMMixer:
    """The mixer's pieces over `self.config` (an `SSMDims` with
    `conv_width`, `chunk`, `a_log_init`, `dt_bias_init`, `d_init`,
    `norm_eps`) and a layer's leaves (`ssm_shapes`)."""

    # a vector over W_in's columns that its product is multiplied by
    ssm_column_scale = None
    # the gated norm's order: the norm of y, then the gate
    ssm_norm_before_gate = False

    def ssm_shapes(self, std: float, out_std: float) -> Dict[
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

    @R.region(R.MIXER_IN)
    def _ssm_project(self, layer: Params, u):
        """u (n, e) -> (z (n, H x P), xBC (n, channels) before the
        convolution, dt (n, H) before the softplus)."""
        c = self.config
        proj = u @ layer["w_in"].astype(c.activation_dtype)
        if self.ssm_column_scale is not None:
            proj = proj * self.ssm_column_scale
        return jnp.split(proj, [c.ssm_inner, c.ssm_inner + c.conv_channels],
                         axis=-1)

    @R.region(R.MIXER_IN)
    def _ssm_inputs(self, layer: Params, mixed, dt):
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
    def _ssm_out(self, layer: Params, y, x, z):
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

        y = normed(y) * gate if self.ssm_norm_before_gate else normed(
            y * gate)
        ad = c.activation_dtype
        return y.astype(ad) @ layer["w_out"].astype(ad)

    def _ssm_seq(self, layer: Params, u, true_len=None):
        """The mixer over one sequence u (s, e). With a `true_len` (a
        prefill's padded bucket) through `ssd_prefill`, the kernel where
        there is one; without, through the plain chunked form, which JAX
        differentiates. Returns (the output after W_out (s, e), the state
        at the sequence's end (N, H x P) float32, the convolution's
        tail)."""
        c = self.config
        s = u.shape[0]
        z, xbc, dt = self._ssm_project(layer, u)
        with R.region(R.MIXER_IN):
            mixed, tail = causal_conv(xbc, layer["conv"], true_len,
                                      layer["conv_bias"])
            x, Bm, Cm, dt, A = self._ssm_inputs(layer, mixed, dt)
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
        return self._ssm_out(layer, y, x, z), state, tail

    def _ssm_step(self, layer: Params, u, pools: Cache, li: int, slot):
        """One decode position a lane, u (B, e): the tails and states at
        row `li` of the pools advanced in place at the lanes' `slot`s (-1:
        left alone). Returns (the output after W_out (B, e), the two
        pools)."""
        c = self.config
        z, xbc, dt = self._ssm_project(layer, u)
        with R.region(R.MIXER_IN):
            conv, tail = _gd.conv_tail_step(
                xbc, layer["conv"], pools["tail"], li, slot,
                layer["conv_bias"])
        xs, Bm, Cm, dt, A = self._ssm_inputs(layer, conv, dt)
        with R.region(R.MIXER_CORE):
            y, state = _ssd.ssd_step(xs, Bm, Cm, dt, A, pools["state"], li,
                                     slot, c.ssm_groups)
        return self._ssm_out(layer, y, xs, z), {"tail": tail, "state": state}

    def ssm_layer_bytes(self, dtype=None) -> int:
        """Bytes one mixer keeps of one sequence, whatever its length: a
        float32 state and the convolution's tail, as the pools hold them
        (`tail_shape`: whole tiles of rows)."""
        c = self.config
        dt = jnp.dtype(dtype or c.activation_dtype)
        return (c.ssm_state * c.ssm_inner * 4
                + math.prod(_gd.tail_shape(c.conv_width, c.conv_channels))
                * dt.itemsize)

    def ssm_pools(self, layers: int, slots: int, dtype) -> Cache:
        """The zeroed pools of `layers` mixers and `slots` slots (nobody's
        among them), inside `init_cache`'s jit."""
        c = self.config
        return {"state": jnp.zeros((layers, slots, c.ssm_state, c.ssm_inner),
                                   jnp.float32),
                "tail": jnp.zeros((layers, slots) + _gd.tail_shape(
                    c.conv_width, c.conv_channels), dtype)}

    def ssm_step_name(self) -> str:
        """The name of a decode step's recurrence, for
        `decode_attention`."""
        c = self.config
        return (_ssd.KERNEL_STEP if _ssd.uses_step_kernel(
            c.ssm_inner, c.ssm_inner // c.ssm_groups, c.ssm_state)
            else "ssd_gather")
