"""Everything the yardstick knows of one architecture: the decoder whose
every layer runs a Mamba-2 state-space mixer and grouped-query attention
side by side on one normed input and sums them, then a SwiGLU feed-forward,
every projection scaled by a published scalar (`falcon_h1`:
Falcon-H1-34B-Instruct, 72 such layers). `benchmarks/models/dense_gqa.py`
states the interface this file implements (`harness/modelcfg.INTERFACE`).

The layers, as the reference computes them (float32, precision `highest`,
nothing of the program imported). `N_1`, `N_2`, `N_f` RMSNorms
(`rms_norm_eps`), no bias anywhere but the convolution's:

    x_0 = embedding_multiplier * E[token]
    h = N_1(x)
    x = x + ssm_out_multiplier * SSM(ssm_in_multiplier * h)
          + attention_out_multiplier * Attn(attention_in_multiplier * h)
    x = x + MLP(N_2(x))
    logits = lm_head_multiplier * (N_f(x) W_head)

- `SSM(u)`, with H = `mamba_n_heads` heads of P = `mamba_d_head`, G =
  `mamba_n_groups`, N = `mamba_d_state`: `[z | xBC | dt] = (u W_in) * m`,
  `m` the vector that holds `ssm_multipliers[0..4]` over the z, x, B, C and
  dt columns; a causal depthwise convolution of width `mamba_d_conv` with
  bias over the channels of xBC, written as four shifted sums, then SiLU;
  `[x | B | C] = xBC` (head i reads group i // (H / G)); `dt = softplus(dt
  + dt_bias)`, `a_t = exp(-exp(A_log) dt_t)`, one number a head; the
  recurrence **position by position**, a `lax.scan` over the float32 state
  h (P x N a head): `h = a_t h + dt_t x_t B_t^T`, `y_t = h C_t + D x_t`;
  `y = RMSNorm(y * SiLU(z))` over each group's H P / G channels
  (`mamba_norm_before_gate` false; `RMSNorm(y) * SiLU(z)` where true);
  `out = y W_out`.
- `Attn(u)`: `q = u W_q` (heads of `head_dim`), `k = key_multiplier * (u
  W_k)`, `v = u W_v` (kv heads); q and k rotated over the whole head at
  `rope_theta`, the key after its scale; scores `q_i . k_j / sqrt(head_dim)`
  for `j <= i`, the mask written out, one head at a time; softmax; `W_o`.
- `MLP(h) = (SiLU(mlp_multipliers[0] * (h W_gate)) * (h W_up)) W_down *
  mlp_multipliers[1]`.

Departures from the published description, none with effect on the
mathematics: norm scales are stored as w with the layer multiplying by
(1 + w), and `A_log`, `dt_bias`, `D` as offsets from the configuration's
`mamba_a_log_init`, `mamba_dt_bias_init`, `mamba_d_init`, both the
program's convention, so one set of seeded zero-mean weights feeds both and
gives decays a trained layer has. **The seeded weights are drawn at a
standard layer's size over their multipliers** (`weight_shapes`): a matrix
times the scalars on its path has the std a layer without multipliers is
seeded with, as a trained maximal-update model's stored weights are its
effective ones over its multipliers. What `config.json` leaves to the
modelling code is listed in the configuration file under `assumed`.

`reference_rows` runs each layer as one jitted program and lifts the large
matrices to float32 where they are multiplied: it has to fit beside 10.5 GB
of served weights and the pools.

`Sizes` holds the published sizes. Of its fields the harness reads `vocab`;
the metrics read this module's `full_decode_call`, `ssd_step_call`,
`ssd_chunk_call`, and `kv_dim`, `of_kind` (every layer is both an `M` and a
`*` layer to a reader that counts layers by kind).

The weight tree has the program's layout (`ray_tpu/models/
parallel_hybrid.py`): layers held one by one in a list.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import (F32, HIGHEST, _ident, _mm, _rms,
                                          _rope, fp8_round)

SSM, ATTENTION = "M", "*"
HEAD_BLOCKS = 8         # column blocks the output head is multiplied in


# ------------------------------------------------------------ sizes
@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    layers: int
    heads: int                      # attention's query heads
    kv_heads: int
    head_dim: int
    rope_theta: float
    m_heads: int                    # the state-space mixer's heads
    m_head_dim: int
    groups: int                     # groups of heads that share B and C
    state: int                      # N: a channel's state
    conv: int                       # the convolution's width
    chunk: int                      # positions a prefill chunk
    norm_before_gate: bool
    a_log_init: float
    dt_bias_init: float
    d_init: float
    d_ff: int
    norm_eps: float
    embedding_multiplier: float
    lm_head_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    ssm_multipliers: Tuple[float, ...]      # on z, x, B, C, dt
    mlp_multipliers: Tuple[float, ...]      # gate's input, down's output

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def d_inner(self) -> int:       # the state-space mixer's width
        return self.m_heads * self.m_head_dim

    @property
    def bc_dim(self) -> int:
        return self.groups * self.state

    @property
    def channels(self) -> int:      # what the convolution runs over
        return self.d_inner + 2 * self.bc_dim

    def of_kind(self, kind: str) -> Tuple[int, ...]:
        """Every layer holds both mixers."""
        return tuple(range(self.layers)) if kind in (SSM, ATTENTION) else ()


MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")


def sizes(cfg: dict) -> Sizes:
    for key, want in (("hidden_act", "silu"), ("mamba_conv_bias", True),
                      ("mamba_proj_bias", False), ("attention_bias", False),
                      ("mlp_bias", False), ("projectors_bias", False),
                      ("mamba_rms_norm", True), ("attn_layer_indices", None),
                      ("rope_scaling", None),
                      ("tie_word_embeddings", False),
                      ("mamba_d_ssm",
                       cfg["mamba_n_heads"] * cfg["mamba_d_head"])):
        if cfg[key] != want:
            raise ValueError(f"{key} {cfg[key]!r}: only {want!r} is "
                             f"written down here")
    return Sizes(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), m_heads=cfg["mamba_n_heads"],
        m_head_dim=cfg["mamba_d_head"], groups=cfg["mamba_n_groups"],
        state=cfg["mamba_d_state"], conv=cfg["mamba_d_conv"],
        chunk=cfg["mamba_chunk_size"],
        norm_before_gate=bool(cfg["mamba_norm_before_gate"]),
        a_log_init=float(cfg["mamba_a_log_init"]),
        dt_bias_init=float(cfg["mamba_dt_bias_init"]),
        d_init=float(cfg["mamba_d_init"]), d_ff=cfg["intermediate_size"],
        norm_eps=float(cfg["rms_norm_eps"]),
        ssm_multipliers=tuple(float(m) for m in cfg["ssm_multipliers"]),
        mlp_multipliers=tuple(float(m) for m in cfg["mlp_multipliers"]),
        **{name: float(cfg[name]) for name in MULTIPLIERS})


def tiny(cfg: dict) -> dict:
    """The same file at rehearsal size: control flow on the CPU, never a
    measurement. Every mechanism stays: two layers of both mixers, 10 query
    heads over 2 kv heads (a group of 5), 4 state-space heads of 8 in 2
    groups with a state of 16, chunks of 8, every multiplier as
    published."""
    small = dict(cfg)
    small.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=10,
                 num_key_value_heads=2, head_dim=16, mamba_n_heads=4,
                 mamba_d_head=8, mamba_d_ssm=32, mamba_n_groups=2,
                 mamba_d_state=16, mamba_chunk_size=8, intermediate_size=96,
                 vocab_size=512)
    return small


# ------------------------------------------------------------ weights
def weight_shapes(s: Sizes) -> dict:
    """A matrix is drawn at a standard layer's std over the multipliers on
    its path, so that the product with them is a standard layer's: 0.02 for
    what reads the stream (`wq`, `wv`, `up`, and `wk` over the key's, `gate`
    over the gate's, `w_in` over `ssm_in_multiplier` and the z columns' of
    `ssm_multipliers`: its x, B, C and dt columns then stand at 0.014, 0.010,
    0.028 and 0.020), 0.02 / sqrt(2 L) for what writes it (`wo`, `w_out`,
    `down`, each over its output multiplier), the embedding and the head
    0.02 over theirs. Norm scales 0.1 around the identity; the convolution's
    taps 0.5 (they pass their input at about its size) and its bias 0.1;
    `a_log` 0.7 and `dt_bias` 1.0 around the configuration's initial values,
    the spread of the family's own initialisation (A uniform in (1, 16), the
    step log-uniform in (0.001, 0.1)), `d` 0.1 around 1, so that the heads'
    decays differ as a trained layer's do. With every matrix at 0.02 as
    stored, the mixers and the feed-forward would add a hundredth of the
    embedding to the stream, and the comparison would read the embedding
    and the head alone."""
    e = s.d_model
    std = 0.02
    out_std = std / math.sqrt(2 * s.layers)
    a_in = s.attention_in_multiplier
    H = s.m_heads
    layer = {
        "norm": ((e,), 0.1), "wq": ((e, s.q_dim), std / a_in),
        "wk": ((e, s.kv_dim), std / (a_in * s.key_multiplier)),
        "wv": ((e, s.kv_dim), std / a_in),
        "wo": ((s.q_dim, e), out_std / s.attention_out_multiplier),
        "w_in": ((e, s.d_inner + s.channels + H),
                 std / (s.ssm_in_multiplier * s.ssm_multipliers[0])),
        "conv": ((s.conv, s.channels), 0.5),
        "conv_bias": ((s.channels,), 0.1),
        "a_log": ((H,), 0.7), "dt_bias": ((H,), 1.0), "d": ((H,), 0.1),
        "gate_norm": ((s.d_inner,), 0.1),
        "w_out": ((s.d_inner, e), out_std / s.ssm_out_multiplier),
        "mlp_norm": ((e,), 0.1),
        "gate": ((e, s.d_ff), std / s.mlp_multipliers[0]),
        "up": ((e, s.d_ff), std),
        "down": ((s.d_ff, e), out_std / s.mlp_multipliers[1])}
    return {"embed": ((s.vocab, e), std / s.embedding_multiplier),
            "final_norm": ((e,), 0.1),
            "lm_head": ((e, s.vocab), std / s.lm_head_multiplier),
            "layers": [dict(layer) for _ in range(s.layers)]}


def param_count(s: Sizes) -> int:
    from benchmarks.harness import weights
    return weights.param_count(weight_shapes(s))


# ------------------------------------------------------------ the program
def program_config(cfg: dict, max_seq_len: int, **extra):
    """The program's ParallelHybridConfig for this file."""
    from ray_tpu.models.parallel_hybrid import ParallelHybridConfig
    s = sizes(cfg)
    dtype = cfg.get("torch_dtype", "bfloat16")
    return ParallelHybridConfig(
        vocab_size=s.vocab, d_model=s.d_model, n_layers=s.layers,
        n_heads=s.heads, n_kv_heads=s.kv_heads, head_dim=s.head_dim,
        rope_theta=s.rope_theta, ssm_heads=s.m_heads,
        ssm_head_dim=s.m_head_dim, ssm_groups=s.groups, ssm_state=s.state,
        conv_width=s.conv, chunk=s.chunk,
        mamba_norm_before_gate=s.norm_before_gate,
        a_log_init=s.a_log_init, dt_bias_init=s.dt_bias_init,
        d_init=s.d_init, d_ff=s.d_ff, max_seq_len=max_seq_len,
        norm_eps=s.norm_eps, ssm_multipliers=s.ssm_multipliers,
        mlp_multipliers=s.mlp_multipliers,
        **{name: getattr(s, name) for name in MULTIPLIERS},
        **{"dtype": dtype, "param_dtype": dtype, **extra})


def train_model(cfg: dict, seq_len: int):
    from ray_tpu.models.parallel_hybrid import ParallelHybrid
    return ParallelHybrid(program_config(cfg, max_seq_len=seq_len))


# ------------------------------------------------------------ the reference
def _w(layer, name):
    return layer[name].astype(F32)


def _conv_silu(x, w, b):
    """x (n, channels), w (width, channels), b (channels,): `y_t = silu(b +
    sum_i w_i x_{t - width + 1 + i})`, zeros before the sequence, as
    shifted sums."""
    n, width = x.shape[0], w.shape[0]
    padded = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return jax.nn.silu(b + sum(w[i] * padded[i:i + n] for i in range(width)))


def _scan(s: Sizes, x, Bm, Cm, dt, A):
    """The selective scan position by position: x (n, H, P), Bm, Cm (n, G,
    N), dt (n, H), A (H,). Returns h C (n, H, P), without the skip."""
    per = s.m_heads // s.groups

    def step(h, inp):
        xt, bt, ct, dtt = inp
        bh, ch = jnp.repeat(bt, per, axis=0), jnp.repeat(ct, per, axis=0)
        h = (jnp.exp(-A * dtt)[:, None, None] * h
             + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, ch, precision=HIGHEST)

    h0 = jnp.zeros((s.m_heads, s.m_head_dim, s.state), F32)
    return jax.lax.scan(step, h0, (x, Bm, Cm, dt))[1]


def column_scale(s: Sizes):
    """`ssm_multipliers` over `W_in`'s columns [z | x | B | C | dt]."""
    widths = (s.d_inner, s.d_inner, s.bc_dim, s.bc_dim, s.m_heads)
    return jnp.concatenate([jnp.full((n,), m, F32)
                            for n, m in zip(widths, s.ssm_multipliers)])


def _mamba(s: Sizes, u, layer, quant):
    """The state-space mixer on one sequence: u (n, d_model) f32, normed
    and scaled by `ssm_in_multiplier`."""
    n, H, G = u.shape[0], s.m_heads, s.groups
    z, xbc, dt = jnp.split(
        _mm(u, _w(layer, "w_in"), quant) * column_scale(s),
        [s.d_inner, s.d_inner + s.channels], axis=-1)
    xbc = _conv_silu(xbc, _w(layer, "conv"), _w(layer, "conv_bias"))
    x, Bm, Cm = jnp.split(xbc, [s.d_inner, s.d_inner + s.bc_dim], axis=-1)
    x = x.reshape(n, H, s.m_head_dim)
    dt = jax.nn.softplus(dt + s.dt_bias_init + _w(layer, "dt_bias"))
    A = jnp.exp(s.a_log_init + _w(layer, "a_log"))
    y = _scan(s, quant(x), quant(Bm.reshape(n, G, s.state)),
              quant(Cm.reshape(n, G, s.state)), dt, A)
    y = (y + (s.d_init + _w(layer, "d"))[:, None] * x).reshape(n, s.d_inner)
    gate = jax.nn.silu(z)

    def normed(a):
        return _rms(a.reshape(n, G, -1),
                    _w(layer, "gate_norm").reshape(G, -1),
                    s.norm_eps).reshape(n, s.d_inner)

    y = normed(y) * gate if s.norm_before_gate else normed(y * gate)
    return _mm(y, _w(layer, "w_out"), quant)


def _attention(s: Sizes, u, layer, quant, remat=False):
    """Grouped-query attention on one sequence: u (n, d_model) f32, normed
    and scaled by `attention_in_multiplier`."""
    n, hd = u.shape[0], s.head_dim
    at = jnp.arange(n)
    q = _mm(u, _w(layer, "wq"), quant).reshape(n, s.heads, hd)
    k = (_mm(u, _w(layer, "wk"), quant) * s.key_multiplier).reshape(
        n, s.kv_heads, hd)
    v = _mm(u, _w(layer, "wv"), quant).reshape(n, s.kv_heads, hd)
    q, k = _rope(q, at, s.rope_theta), _rope(k, at, s.rope_theta)
    seen = at[:, None] >= at[None, :]
    group = s.heads // s.kv_heads

    def one_head(hq):
        """One head at a time, so that the (seq, seq) scores of all heads
        never exist together."""
        head, qh = hq
        kh = jnp.take(k, head // group, axis=1)
        vh = jnp.take(v, head // group, axis=1)
        scores = jnp.einsum("qd,kd->qk", quant(qh), quant(kh),
                            precision=HIGHEST) / (hd ** 0.5)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("qk,kd->qd", quant(probs), quant(vh),
                          precision=HIGHEST)

    if remat:
        one_head = jax.checkpoint(one_head)
    out = jax.lax.map(one_head, (jnp.arange(s.heads), q.transpose(1, 0, 2)))
    return _mm(out.transpose(1, 0, 2).reshape(n, s.q_dim), _w(layer, "wo"),
               quant)


def _mlp(s: Sizes, h, layer, quant):
    gate = jax.nn.silu(_mm(h, _w(layer, "gate"), quant)
                       * s.mlp_multipliers[0])
    return _mm(gate * _mm(h, _w(layer, "up"), quant), _w(layer, "down"),
               quant) * s.mlp_multipliers[1]


def _block(s: Sizes, x, layer, quant, remat=False):
    """A layer on one sequence: x (seq, d_model) f32. A matrix is lifted to
    float32 where it is multiplied."""
    h = _rms(x, _w(layer, "norm"), s.norm_eps)
    x = (x + s.ssm_out_multiplier * _mamba(
        s, s.ssm_in_multiplier * h, layer, quant)
        + s.attention_out_multiplier * _attention(
            s, s.attention_in_multiplier * h, layer, quant, remat))
    return x + _mlp(s, _rms(x, _w(layer, "mlp_norm"), s.norm_eps), layer,
                    quant)


def _head(s: Sizes, x, norm, w, quant, window=None):
    """The final norm and the scaled head, a block of columns at a time,
    each lifted to float32 alone."""
    if window is not None:
        x = jax.lax.dynamic_slice_in_dim(x, window[0], window[1], axis=0)
    x = _rms(x, norm.astype(F32), s.norm_eps)
    vocab = w.shape[1]
    blocks = HEAD_BLOCKS if vocab % HEAD_BLOCKS == 0 else 1
    width = vocab // blocks

    def one(j):
        cols = jax.lax.dynamic_slice_in_dim(w, j * width, width, axis=1)
        return _mm(x, cols.astype(F32), quant)

    out = jax.lax.map(one, jnp.arange(blocks))       # (blocks, rows, width)
    return s.lm_head_multiplier * out.transpose(1, 0, 2).reshape(
        x.shape[0], vocab)


def _embed(s: Sizes, params, tokens):
    return s.embedding_multiplier * params["embed"][tokens].astype(F32)


def logits_fn(s: Sizes, params, tokens, quant=_ident, window=None,
              remat=False):
    """tokens (seq,) int32 -> logits (seq, vocab) f32 of one sequence, or
    of the `window` = (start, rows) of its positions."""
    x = _embed(s, params, tokens)
    for layer in params["layers"]:
        block = functools.partial(_block, s, quant=quant, remat=remat)
        if remat:       # the backward keeps one layer's activations
            block = jax.checkpoint(block)
        x = block(x, layer)
    return _head(s, x, params["final_norm"], params["lm_head"], quant,
                 window)


def loss_fn(s: Sizes, params, tokens, quant=_ident, remat=False):
    """Mean next-token cross-entropy of one sequence, tokens (seq,)."""
    logits = logits_fn(s, params, tokens, quant, remat=remat)[:-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return -jnp.mean(picked)


_QUANT = {False: _ident, True: fp8_round}
_jit_block = jax.jit(
    lambda s, x, layer, control: _block(s, x, layer, _QUANT[control]),
    static_argnums=(0, 3))
_jit_head = jax.jit(
    lambda s, x, norm, head, start, rows, control: _head(
        s, x, norm, head, _QUANT[control], (start, rows)),
    static_argnums=(0, 5, 6))


def reference_rows(s: Sizes, params, tokens, start, rows: int,
                   control: bool = False):
    """Logits of positions start .. start + rows - 1 of one sequence that
    is padded at its end (every layer is causal, so the padding touches
    nothing before it). `control` rounds every matmul operand to fp8
    instead, the scan's x, B and C and attention's q, k, v and
    probabilities among them; the decays, dt and the state stay float32 in
    both. One jitted program, run layer by layer (this module's docstring
    says why)."""
    x = _embed(s, params, tokens)
    for layer in params["layers"]:
        x = _jit_block(s, x, layer, control)
    return _jit_head(s, x, params["final_norm"], params["lm_head"], start,
                     rows, control)


# ------------------------------------------------------------ required ops
def matmul_params(s: Sizes) -> float:
    """Parameters that multiply a token's activations: every layer's
    attention, state-space and feed-forward projections, and the output
    head. Not the embedding table, the norms, the convolution's taps or the
    scan's constants."""
    layer = (2 * s.d_model * s.q_dim + 2 * s.d_model * s.kv_dim
             + s.d_model * (s.d_inner + s.channels + s.m_heads)
             + s.d_inner * s.d_model + 3 * s.d_model * s.d_ff)
    return float(s.layers * layer + s.d_model * s.vocab)


def _scan_flops(s: Sizes) -> float:
    """One position of one layer's scan, position by position: the decay,
    the outer product and its add, `h C` (a multiply and an add), each over
    a layer's d_inner x N."""
    return 5.0 * s.d_inner * s.state


def attention_flops_per_token(s: Sizes, seq_len: int,
                              passes: int = 3) -> float:
    """The mixers' own work per token, all layers: attention's causal QK^T
    and PV (2 x head_dim operations each a head and key seen) and the
    scan's recurrence, both in every layer; the backward is twice the
    forward (`passes` 3)."""
    full = 4.0 * s.head_dim * s.heads * (seq_len + 1) / 2.0
    return passes * s.layers * (full + _scan_flops(s))


def train_flops_per_token(s: Sizes, seq_len: int) -> float:
    """Forward + backward: 6 per matmul parameter plus the mixers."""
    return 6.0 * matmul_params(s) + attention_flops_per_token(s, seq_len)


def full_decode_call(s: Sizes, live_positions: int, lanes: int,
                     itemsize: int = 2) -> dict:
    """Every layer's decode attention over `live_positions` cache positions
    a layer (`engine.decode_dispatch`'s, summed over lanes and steps): each
    position's key and value read once a layer, each lane's queries in and
    outputs out; QK^T and PV 2 x head_dim operations each a query head and
    position, five query heads a kv head. A page's unused tail, which the
    kernel copies too, does not count."""
    n = s.layers
    return {"flops": n * 4.0 * live_positions * s.q_dim,
            "bytes": float(n * (2 * live_positions * s.kv_dim
                                + 2 * lanes * s.q_dim) * itemsize)}


def ssd_step_call(s: Sizes, state_slots: int, itemsize: int = 2) -> dict:
    """Every layer's decode recurrence for `state_slots` lane-steps
    (`engine.decode_dispatch`'s `state_slots`, summed over steps), as the
    kernel's events hold it, whatever blocks it is cut in: a layer's
    float32 state (N x d_inner: 4.19 MB) read and written, x, B and C and a
    step a head in, the float32 outputs out. Bytes bound it. The gate z,
    the skip and the norm are applied outside the kernel's events and are
    not counted, nor is the convolution's tail, which is gathered and
    scattered beside it."""
    state = s.state * s.d_inner * 4
    io = s.channels * itemsize + s.m_heads * 4 + s.d_inner * 4
    return {"flops": s.layers * state_slots * _scan_flops(s),
            "bytes": float(s.layers * state_slots * (2 * state + io))}


def ssd_chunk_call(s: Sizes, tokens: int, itemsize: int = 2) -> dict:
    """One prefill's chunked scan over all layers at `tokens` true
    positions, chunks of C = `chunk`, as the algorithm needs it a chunk:
    the lower triangle of `C B^T` once a group (C^2 N), a head's masked
    triangle times its x (C^2 P), and its two products with the state, `C
    h_0^T` and `X^T B` (2 C N P each); x, B, C and the steps read, the
    outputs written, the last state written once. What a padded bucket
    holds past the prompt is skipped or masked: the program's cost."""
    C = s.chunk
    per_chunk = (s.groups * C * C * s.state + s.m_heads * (
        C * C * s.m_head_dim + 4.0 * C * s.state * s.m_head_dim))
    nbytes = (tokens * ((s.channels + s.d_inner) * itemsize + s.m_heads * 4)
              + s.state * s.d_inner * 4)
    return {"flops": s.layers * tokens / float(C) * per_chunk,
            "bytes": float(s.layers * nbytes)}
