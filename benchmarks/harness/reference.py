"""The plain reference: a decoder-only transformer (RMSNorm, grouped-query
attention with rotary positions, SwiGLU) in straightforward float32
`jax.numpy`, matmul precision `highest`, no kernels, no cache, no batching
tricks. Independent of `ray_tpu/models/`: it shares only the layout of the
weight tree, which `benchmarks/harness/weights.py` makes from the seed.

Departures from the published descriptions, both without effect on the
mathematics: norm scales are stored as w with the layer multiplying by
(1 + w); InternLM2's fused wqkv is held as three matrices.

`quant` puts the control in the reference's place: every matmul operand
(weights, activations, keys and values) is rounded to the precision below
the configuration's (fp8 e4m3 with a per-tensor scale) before use.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.harness.modelcfg import Sizes

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _ident(x):
    return x


@jax.custom_vjp
def fp8_round(x):
    """x rounded to fp8 e4m3 under a per-tensor scale; the gradient passes
    straight through, as a quantised training path would let it."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


fp8_round.defvjp(lambda x: (fp8_round(x), None), lambda _, g: (g,))


def _mm(a, b, quant):
    return jnp.matmul(quant(a), quant(b), precision=HIGHEST)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, positions, theta):
    """x (s, heads, hd); split-halves pairing (i with i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = positions.astype(F32)[:, None, None] * inv
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(s: Sizes, x, layer, positions, quant, remat=False):
    """One layer on one sequence: x (seq, d_model) f32."""
    layer = jax.tree_util.tree_map(lambda a: a.astype(F32), layer)
    n = x.shape[0]
    h = _rms(x, layer["attn_norm"], s.norm_eps)
    q = _mm(h, layer["wq"], quant).reshape(n, s.heads, s.head_dim)
    k = _mm(h, layer["wk"], quant).reshape(n, s.kv_heads, s.head_dim)
    v = _mm(h, layer["wv"], quant).reshape(n, s.kv_heads, s.head_dim)
    q = _rope(q, positions, s.rope_theta)
    k = _rope(k, positions, s.rope_theta)
    group = s.heads // s.kv_heads
    causal = positions[:, None] >= positions[None, :]

    def one_kv_head(qkv):
        """The `group` query heads that share one key/value head; heads are
        walked one kv head at a time so the (seq, seq) scores of all heads
        never exist together."""
        qg, kh, vh = qkv                    # (n, group, hd), (n, hd), (n, hd)
        scores = jnp.einsum("qgd,kd->gqk", quant(qg), quant(kh),
                            precision=HIGHEST) / (s.head_dim ** 0.5)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("gqk,kd->qgd", quant(probs), quant(vh),
                          precision=HIGHEST)

    qg = q.reshape(n, s.kv_heads, group, s.head_dim).transpose(1, 0, 2, 3)
    if remat:
        one_kv_head = jax.checkpoint(one_kv_head)
    attn = jax.lax.map(one_kv_head, (qg, k.transpose(1, 0, 2),
                                     v.transpose(1, 0, 2)))
    attn = attn.transpose(1, 0, 2, 3).reshape(n, s.q_dim)
    x = x + _mm(attn, layer["wo"], quant)
    h = _rms(x, layer["mlp_norm"], s.norm_eps)
    gate = jax.nn.silu(_mm(h, layer["gate"], quant))
    up = _mm(h, layer["up"], quant)
    return x + _mm(gate * up, layer["down"], quant)


def logits_fn(s: Sizes, params, tokens, quant=_ident, window=None,
              remat=False):
    """tokens (seq,) int32 -> logits (seq, vocab) f32 of one sequence, or
    of the `window` = (start, rows) of its positions. The layers are walked by a scan that lifts
    one layer's weights to f32 at a time, so the f32 copy of a whole model
    never exists."""
    positions = jnp.arange(tokens.shape[0])
    x = params["embed"].astype(F32)[tokens]

    def body(x, layer):
        return _block(s, x, layer, positions, quant, remat), None

    if remat:       # the backward keeps one layer's activations at a time
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    if window is not None:
        x = jax.lax.dynamic_slice_in_dim(x, window[0], window[1], axis=0)
    x = _rms(x, params["final_norm"].astype(F32), s.norm_eps)
    head = params["embed"].T if s.tied else params["lm_head"]
    return _mm(x, head.astype(F32), quant)


def loss_fn(s: Sizes, params, tokens, quant=_ident, remat=False):
    """Mean next-token cross-entropy of one sequence, tokens (seq,)."""
    logits = logits_fn(s, params, tokens, quant, remat=remat)[:-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return -jnp.mean(picked)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def reference_rows(s: Sizes, params, tokens, start, rows: int,
                   control: bool = False):
    """Logits of positions start .. start + rows - 1 of one sequence that
    is padded at its end (causal, so the padding touches nothing before
    it). One program serves every prompt length. `control` rounds every
    matmul operand to fp8 instead."""
    quant = fp8_round if control else _ident
    return logits_fn(s, params, tokens, quant, window=(start, rows))


def rel_rms(a, b) -> float:
    """||a - b|| / ||b||, b the reference."""
    a = jnp.asarray(a, F32)
    b = jnp.asarray(b, F32)
    return float(jnp.sqrt(jnp.sum(jnp.square(a - b))
                          / jnp.sum(jnp.square(b))))
