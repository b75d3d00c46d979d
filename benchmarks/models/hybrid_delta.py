"""Everything the yardstick knows of one architecture: the decoder whose
layers are gated delta-rule layers (linear attention: a recurrent state of
one size a sequence) or full multi-head attention layers, named one by one
(`olmo_hybrid`: Olmo-Hybrid-7B, three linear layers to one full), in
post-norm blocks with a SwiGLU feed-forward. `benchmarks/models/
dense_gqa.py` states the interface this file implements
(`harness/modelcfg.INTERFACE`).

The layers, as the reference computes them (float32, precision `highest`,
nothing of the program imported), x (T, hidden) a layer's input:

- block: `x <- x + RMSNorm(Mixer(x))`, then `x <- x + RMSNorm(MLP(x))`,
  the MLP a SwiGLU; a final RMSNorm before the untied head.
- linear layer, H heads of key width dk and value width dv: `[q~ | k~ |
  v~] = x W_qkv`, `z = x W_z`, `[a | b] = x W_ab`; a causal depthwise
  convolution of width 4 along the sequence over the channels of `[q~ | k~
  | v~]`, written as four shifted sums, then SiLU: q, k, v; a head's `q <-
  q / |q| / sqrt(dk)`, `k <- k / |k|`; `beta = 2 sigmoid(b)`
  (`linear_allow_neg_eigval`; `sigmoid(b)` without), `g = -exp(A_log)
  softplus(a + dt_bias)`; the recurrence **position by position**, a
  `lax.scan` over the float32 state S (dk x dv a head): `S' = exp(g_t) S`,
  `S = S' + beta_t k_t (v_t - S'^T k_t)^T`, `o_t = S^T q_t`; `y =
  (RMSNorm_dv(o) * SiLU(z)) W_o`, the norm a head over its dv.
- full layer: `q = RMSNorm(x W_q)`, `k = RMSNorm(x W_k)` over their whole
  width, `v = x W_v`; heads split; scores `q_i . k_j / sqrt(head_dim)` for
  `j <= i`, the mask written out, one head at a time; softmax; `W_o`. No
  rotary embedding.

Departures from the published description, none with effect on the
mathematics: norm scales are stored as w with the layer multiplying by
(1 + w), and `A_log`, `dt_bias` as offsets from the configuration's
`linear_a_log_init`, `linear_dt_bias_init` (the middle of the family's
initialisation), both the program's convention, so one set of seeded
zero-mean weights feeds both and gives decays a trained layer has; the
output head is multiplied a block of columns at a time, lifted to float32
alone, so that 1.5 GB of float32 head never lies beside the served
weights and cache (the control rounds each block under its own scale).
What `config.json` leaves to the modelling code is listed in the
configuration file under `assumed`.

`Sizes` holds the published sizes by kind of layer (`layer_types`, the
full layers' `heads` / `kv_heads` / `head_dim`, the linear layers'
`lin_heads` / `dk` / `dv` / `conv`, `chunk`). Of its fields the harness
reads `vocab`; `kernel.full_decode_roofline.mixed8k` reads this module's
`full_decode_call` (the full layers, `kv_dim`, `heads`),
`kernel.delta_step_roofline.answers3k` `delta_step_call` (the linear
layers, `dk`, `dv`, `lin_heads`), `kernel.delta_chunk_roofline.answers3k`
`delta_chunk_call` (the same and `chunk`);
`cache.state_bytes_share.answers3k` reads `kv_dim` and the full layers'
count for the bytes of the keys and values a dispatch read.

The weight tree has the program's layout (`ray_tpu/models/
hybrid_delta.py`): layers held one by one in a list.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import (F32, HIGHEST, _ident, _mm, _rms,
                                          fp8_round)

LINEAR, FULL = "linear_attention", "full_attention"
HEAD_BLOCKS = 8         # column blocks the output head is multiplied in


# ------------------------------------------------------------ sizes
@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    heads: int                      # a full layer's query heads
    kv_heads: int
    layer_types: Tuple[str, ...]
    lin_heads: int                  # a linear layer's key = value heads
    dk: int                         # its key head width
    dv: int                         # its value head width
    conv: int                       # its convolution's width
    neg_eigval: bool
    chunk: int                      # positions a prefill chunk
    a_log_init: float
    dt_bias_init: float
    d_ff: int
    norm_eps: float

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def key_dim(self) -> int:
        return self.lin_heads * self.dk

    @property
    def value_dim(self) -> int:
        return self.lin_heads * self.dv

    @property
    def channels(self) -> int:      # what the convolution runs over
        return 2 * self.key_dim + self.value_dim

    def of_kind(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.layer_types) if k == kind)


def sizes(cfg: dict) -> Sizes:
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError(f"layer_types names {len(cfg['layer_types'])} "
                         f"layers, num_hidden_layers "
                         f"{cfg['num_hidden_layers']}")
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("unlike numbers of key and value heads in a "
                         "linear layer are not written here")
    if cfg["rope_parameters"].get("rope_theta") is not None:
        raise ValueError("full layers with a rotary embedding are not "
                         "written here")
    return Sizes(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        layer_types=tuple(cfg["layer_types"]),
        lin_heads=cfg["linear_num_key_heads"],
        dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
        conv=cfg["linear_conv_kernel_dim"],
        neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
        chunk=int(cfg["linear_chunk_size"]),
        a_log_init=float(cfg["linear_a_log_init"]),
        dt_bias_init=float(cfg["linear_dt_bias_init"]),
        d_ff=cfg["intermediate_size"], norm_eps=float(cfg["rms_norm_eps"]))


def tiny(cfg: dict) -> dict:
    """The same file at rehearsal size: control flow on the CPU, never a
    measurement. Every mechanism stays: one period of three linear layers
    and a full one, 4 heads of 8 / 16, chunks of 8. One period and not
    two: a linear layer hands on about twice the relative error it is
    given, so in bfloat16 at this width eight layers read 0.57 against the
    float32 reference where four read 0.043 (PERF.md section 6, PR 37);
    in float32 the program agrees to 2e-4 at either depth."""
    small = dict(cfg)
    small.update(hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=4, num_hidden_layers=4,
                 layer_types=list(cfg["layer_types"][:4]),
                 linear_num_key_heads=4, linear_num_value_heads=4,
                 linear_key_head_dim=8, linear_value_head_dim=16,
                 linear_chunk_size=8, intermediate_size=128, vocab_size=512)
    return small


# ------------------------------------------------------------ weights
def weight_shapes(s: Sizes) -> dict:
    """Normal weights of 0.02, output projections scaled down by depth, norm
    scales 0.1 around the identity; the convolution's four taps 0.5 (they
    pass their input at about its size); `a_log` and `dt_bias` 1.0 around
    the configuration's initial values, the spread of the family's own
    initialisation (A uniform in (0, 16), the step log-uniform in (0.001,
    0.1)), so that the heads' decays differ as a trained layer's do. The
    per-layer layout the program's `HybridDelta` holds."""
    e = s.d_model
    std = 0.02
    out_std = std / math.sqrt(2 * s.layers)

    def layer(i):
        ffn = {"attn_norm": ((e,), 0.1), "mlp_norm": ((e,), 0.1),
               "gate": ((e, s.d_ff), std), "up": ((e, s.d_ff), std),
               "down": ((s.d_ff, e), out_std)}
        if s.layer_types[i] == FULL:
            return {"wq": ((e, e), std), "wk": ((e, s.kv_dim), std),
                    "wv": ((e, s.kv_dim), std), "wo": ((e, e), out_std),
                    "q_norm": ((e,), 0.1), "k_norm": ((s.kv_dim,), 0.1),
                    **ffn}
        H = s.lin_heads
        return {"w_qkv": ((e, s.channels), std),
                "w_z": ((e, s.value_dim), std), "w_ab": ((e, 2 * H), std),
                "conv": ((s.conv, s.channels), 0.5),
                "a_log": ((H,), 1.0), "dt_bias": ((H,), 1.0),
                "o_norm": ((s.dv,), 0.1),
                "wo": ((s.value_dim, e), out_std), **ffn}

    return {"embed": ((s.vocab, e), std), "final_norm": ((e,), 0.1),
            "lm_head": ((e, s.vocab), std),
            "layers": [layer(i) for i in range(s.layers)]}


def param_count(s: Sizes) -> int:
    from benchmarks.harness import weights
    return weights.param_count(weight_shapes(s))


# ------------------------------------------------------------ the program
def program_config(cfg: dict, max_seq_len: int, **extra):
    """The program's HybridDeltaConfig for this file."""
    from ray_tpu.models.hybrid_delta import HybridDeltaConfig
    s = sizes(cfg)
    dtype = cfg.get("torch_dtype", "bfloat16")
    return HybridDeltaConfig(
        vocab_size=s.vocab, d_model=s.d_model, n_heads=s.heads,
        n_kv_heads=s.kv_heads, layer_types=s.layer_types,
        linear_heads=s.lin_heads, linear_key_dim=s.dk,
        linear_value_dim=s.dv, conv_width=s.conv,
        allow_neg_eigval=s.neg_eigval, chunk=s.chunk,
        a_log_init=s.a_log_init, dt_bias_init=s.dt_bias_init, d_ff=s.d_ff,
        max_seq_len=max_seq_len, norm_eps=s.norm_eps,
        **{"dtype": dtype, "param_dtype": dtype, **extra})


def train_model(cfg: dict, seq_len: int):
    from ray_tpu.models.hybrid_delta import HybridDelta
    return HybridDelta(program_config(cfg, max_seq_len=seq_len))


# ------------------------------------------------------------ the reference
def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _conv_silu(x, w):
    """x (n, channels), w (width, channels): `y_t = silu(sum_i w_i x_{t -
    width + 1 + i})`, zeros before the sequence, as shifted sums."""
    n, width = x.shape[0], w.shape[0]
    padded = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[i] * padded[i:i + n] for i in range(width)))


def _recurrence(q, k, v, g, beta):
    """The gated delta rule position by position: q, k (n, H, dk), v (n,
    H, dv), g, beta (n, H). Returns o (n, H, dv)."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[:, None, None]
        mem = jnp.einsum("hkv,hk->hv", S, kt, precision=HIGHEST)
        u = (vt - mem) * bt[:, None]
        S = S + kt[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), F32),
                        (q, k, v, g, beta))
    return o


def _linear(s: Sizes, x, layer, quant):
    """A linear layer's mixer on one sequence: x (n, d_model) f32."""
    n, H = x.shape[0], s.lin_heads
    mixed = _conv_silu(_mm(x, layer["w_qkv"], quant), layer["conv"])
    q, k, v = jnp.split(mixed, [s.key_dim, 2 * s.key_dim], axis=-1)
    q = _l2(q.reshape(n, H, s.dk)) / math.sqrt(s.dk)
    k = _l2(k.reshape(n, H, s.dk))
    ab = _mm(x, layer["w_ab"], quant)
    g = -jnp.exp(s.a_log_init + layer["a_log"]) * jax.nn.softplus(
        ab[:, :H] + s.dt_bias_init + layer["dt_bias"])
    beta = jax.nn.sigmoid(ab[:, H:]) * (2.0 if s.neg_eigval else 1.0)
    o = _recurrence(quant(q), quant(k), quant(v.reshape(n, H, s.dv)), g,
                    beta)
    z = _mm(x, layer["w_z"], quant).reshape(n, H, s.dv)
    y = _rms(o, layer["o_norm"], s.norm_eps) * jax.nn.silu(z)
    return _mm(y.reshape(n, s.value_dim), layer["wo"], quant)


def _full(s: Sizes, x, layer, quant, remat):
    """A full layer's mixer on one sequence: x (n, d_model) f32."""
    n, hd = x.shape[0], s.head_dim
    q = _rms(_mm(x, layer["wq"], quant), layer["q_norm"], s.norm_eps)
    k = _rms(_mm(x, layer["wk"], quant), layer["k_norm"], s.norm_eps)
    v = _mm(x, layer["wv"], quant)
    q = q.reshape(n, s.heads, hd)
    k, v = k.reshape(n, s.kv_heads, hd), v.reshape(n, s.kv_heads, hd)
    at = jnp.arange(n)
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
    return _mm(out.transpose(1, 0, 2).reshape(n, s.d_model), layer["wo"],
               quant)


def _swiglu(h, layer, quant):
    return _mm(jax.nn.silu(_mm(h, layer["gate"], quant))
               * _mm(h, layer["up"], quant), layer["down"], quant)


def _block(s: Sizes, i: int, x, layer, quant, remat=False):
    """Layer i on one sequence: x (seq, d_model) f32."""
    layer = {k: v.astype(F32) for k, v in layer.items()}
    if s.layer_types[i] == FULL:
        mixed = _full(s, x, layer, quant, remat)
    else:
        mixed = _linear(s, x, layer, quant)
    x = x + _rms(mixed, layer["attn_norm"], s.norm_eps)
    return x + _rms(_swiglu(x, layer, quant), layer["mlp_norm"], s.norm_eps)


def _head(x, w, quant):
    """x (rows, d_model) f32 times the head w (d_model, vocab), a block of
    columns at a time, each lifted to float32 alone."""
    vocab = w.shape[1]
    blocks = HEAD_BLOCKS if vocab % HEAD_BLOCKS == 0 else 1
    width = vocab // blocks

    def one(j):
        cols = jax.lax.dynamic_slice_in_dim(w, j * width, width, axis=1)
        return _mm(x, cols.astype(F32), quant)

    out = jax.lax.map(one, jnp.arange(blocks))       # (blocks, rows, width)
    return out.transpose(1, 0, 2).reshape(x.shape[0], vocab)


def logits_fn(s: Sizes, params, tokens, quant=_ident, window=None,
              remat=False):
    """tokens (seq,) int32 -> logits (seq, vocab) f32 of one sequence, or
    of the `window` = (start, rows) of its positions."""
    x = params["embed"].astype(F32)[tokens]
    for i, layer in enumerate(params["layers"]):
        block = functools.partial(_block, s, i, quant=quant, remat=remat)
        if remat:       # the backward keeps one layer's activations
            block = jax.checkpoint(block)
        x = block(x, layer)
    if window is not None:
        x = jax.lax.dynamic_slice_in_dim(x, window[0], window[1], axis=0)
    x = _rms(x, params["final_norm"].astype(F32), s.norm_eps)
    return _head(x, params["lm_head"], quant)


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
    is padded at its end (every layer is causal, so the padding touches
    nothing before it). `control` rounds every matmul operand to fp8
    instead, the recurrence's q, k and v among them; the state stays
    float32 in both."""
    quant = fp8_round if control else _ident
    return logits_fn(s, params, tokens, quant, window=(start, rows))


# ------------------------------------------------------------ required ops
def _mixer_params(s: Sizes, kind: str) -> int:
    if kind == FULL:
        return 2 * s.d_model * s.d_model + 2 * s.d_model * s.kv_dim
    return (s.d_model * (s.channels + s.value_dim + 2 * s.lin_heads)
            + s.value_dim * s.d_model)


def matmul_params(s: Sizes) -> int:
    """Parameters that multiply a token's activations: every layer's
    projections and feed-forward, the output head. Not the embedding
    table, the norms, the convolution's taps or the gates' constants."""
    return (sum(_mixer_params(s, k) + 3 * s.d_model * s.d_ff
                for k in s.layer_types) + s.d_model * s.vocab)


def _recurrence_flops(s: Sizes) -> float:
    """One position of one linear layer, position by position: the decay,
    S'^T k, the rank-one update and S^T q, each over a head's dk x dv."""
    return 7.0 * s.lin_heads * s.dk * s.dv


def attention_flops_per_token(s: Sizes, seq_len: int,
                              passes: int = 3) -> float:
    """The mixers' own work per token, all layers: a full layer's causal
    QK^T and PV (2 x head_dim operations each a head and key seen), a
    linear layer's recurrence; the backward is twice the forward (`passes`
    3)."""
    full = 4.0 * s.head_dim * s.heads * (seq_len + 1) / 2.0
    return passes * (len(s.of_kind(FULL)) * full
                     + len(s.of_kind(LINEAR)) * _recurrence_flops(s))


def train_flops_per_token(s: Sizes, seq_len: int) -> float:
    """Forward + backward: 6 per matmul parameter plus the mixers."""
    return 6.0 * matmul_params(s) + attention_flops_per_token(s, seq_len)


def full_decode_call(s: Sizes, live_positions: int, lanes: int,
                     itemsize: int = 2) -> dict:
    """The full layers' decode attention over `live_positions` cache
    positions a layer (`engine.decode_dispatch`'s, summed over lanes and
    steps): each position's key and value read once a layer, each lane's
    queries in and outputs out; QK^T and PV 2 x head_dim operations each a
    query head and position. A page's unused tail, which the kernel copies
    too, does not count."""
    n = len(s.of_kind(FULL))
    return {"flops": n * 4.0 * live_positions * s.d_model,
            "bytes": float(n * (2 * live_positions * s.kv_dim
                                + 2 * lanes * s.d_model) * itemsize)}


def delta_step_call(s: Sizes, state_slots: int, itemsize: int = 2) -> dict:
    """The linear layers' decode recurrence for `state_slots` lane-steps
    (`engine.decode_dispatch`'s `state_slots`, summed over steps), as the
    kernel's events hold it: a layer's float32 state read and written, q, k
    and v and the two gates in, the float32 outputs out. Bytes bound it.
    The convolution's tail is gathered and scattered outside the kernel's
    events and is not counted here (`cache.state_bytes_share.answers3k`
    counts it)."""
    n = len(s.of_kind(LINEAR))
    state = s.dk * s.value_dim * 4
    io = s.channels * itemsize + 2 * s.lin_heads * 4 + s.value_dim * 4
    return {"flops": n * state_slots * _recurrence_flops(s),
            "bytes": float(n * state_slots * (2 * state + io))}


def delta_chunk_call(s: Sizes, tokens: int, itemsize: int = 2) -> dict:
    """One prefill's chunked recurrence over all linear layers at `tokens`
    true positions, chunks of C = `chunk`, as the algorithm needs it a
    head and chunk: the lower triangles of K K^T and Q K^T (C^2 dk each),
    the unit lower-triangular solve (C^3 / 3), its products with V and K
    and the triangle times U (C^2 (2 dv + dk)), and the three products
    with the state (2 C dk dv each); q, k, v and the gates read, the
    outputs and the last state written. What a padded bucket holds past
    the prompt is skipped or masked: the program's cost."""
    n, C = len(s.of_kind(LINEAR)), s.chunk
    per_chunk = (2.0 * C * C * s.dk + C ** 3 / 3.0
                 + C * C * (2.0 * s.dv + s.dk) + 6.0 * C * s.dk * s.dv)
    chunks = tokens / float(C)
    nbytes = (tokens * (s.channels + s.value_dim) * itemsize
              + tokens * 2 * s.lin_heads * 4 + s.dk * s.value_dim * 4)
    return {"flops": n * s.lin_heads * chunks * per_chunk,
            "bytes": float(n * nbytes)}
