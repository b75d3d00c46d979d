"""Everything the yardstick knows of one architecture: the decoder whose
mixers are gated short convolutions, with grouped-query attention in one
layer of four, over dense or routed feed-forwards and a tied head
(`lfm2_moe`: LFM2-8B-A1B, 24 layers, 18 `conv` and 6 `full_attention`).
`benchmarks/models/dense_gqa.py` states the interface this file implements
(`harness/modelcfg.INTERFACE`).

The layers, as the reference computes them (float32, precision `highest`,
nothing of the program imported). `N_1`, `N_2`, `N_f` RMSNorms
(`norm_eps`), no bias anywhere, `E` the embedding's table:

    x_0 = E[token]
    x = x + Op_l(N_1(x))      Op_l = Conv where layer_types[l] == "conv",
                                     Attn where "full_attention"
    x = x + FF_l(N_2(x))      FF_l = MLP where l < num_dense_layers, else MoE
    logits = N_f(x) E^T       (tied: the embedding's own table)

- `Conv(h)`: `[B | C | u] = h W_in`, three thirds of `hidden_size` in that
  order; `g = B * u`; `c_t = w_0 g_{t-2} + w_1 g_{t-1} + w_2 g_t`
  (depthwise, causal, `conv_L_cache` taps, zeros before the sequence, no
  bias, **no activation**), written as three shifted sums; `out = (C * c)
  W_out`.
- `Attn(h)`: `q = h W_q` (heads of `hidden_size / num_attention_heads`),
  `k = h W_k`, `v = h W_v` (kv heads); `q` and `k` RMS-normed over a head's
  numbers (one weight of a head's width, shared by the heads), then rotated
  over the whole head at `rope_theta`, split halves; scores `q_i . k_j /
  sqrt(head_dim)` for `j <= i`, the mask written out, one head at a time;
  softmax; `W_o`.
- `MoE(h)`: `s = sigmoid(h W_r)`; the `num_experts_per_tok` experts are
  the top of `s + b` (`b` the stored `expert_bias`, where
  `use_expert_bias`); their weights `s` itself at those, over their sum
  **+ 1e-6** (the published epsilon; `norm_topk_prob`), times
  `routed_scaling_factor`; `y = sum_i w_i (SiLU(h W1_i) * (h W3_i)) W2_i`:
  every expert computed for every token, weighted by zero where the token
  did not choose it, one expert lifted to float32 at a time. No shared
  expert.
- `MLP(h) = (SiLU(h W1) * (h W3)) W2`.

Departures from the published description, none with effect on the
mathematics: norm scales are stored as w with the layer multiplying by
(1 + w) (the program's convention, so one set of seeded zero-mean weights
feeds both). What `config.json` leaves to the modelling code is listed in
the configuration file under `assumed`. `expert_bias` is a seeded leaf of
std `BIAS_STD` (a trained model's is learned; zero would leave choice and
weight indistinguishable); the convolution's taps are seeded at 0.5 (they
pass their input at about its size).

`reference_rows` runs each layer as one jitted program (four of them: a
mixer's kind by a feed-forward's) and lifts the large matrices to float32
where they are multiplied: it has to fit beside 10.8 GB of served weights
and the pools.

`Sizes` holds the published sizes. Of its fields the harness reads `vocab`;
the metrics read this module's `full_decode_call`, `flash_prefill_call`,
`moe_gmm_call`, and `kv_dim`, `held`, `of_kind` (`"*"` the attention
layers, `"E"` the layers whose feed-forward is routed).

The weight tree has the program's layout (`ray_tpu/models/
gated_conv_moe.py`): layers held one by one in a list, no `lm_head`.
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

CONV, ATTENTION = "conv", "full_attention"
HEAD_BLOCKS = 8         # row blocks of the table the head is multiplied in
# std of the seeded expert_bias: of 32 sigmoid scores at the published
# widths the 4th and 5th largest lie some 0.018 apart (the median; 0.025
# the mean), and 0.01 moves about one token's choice in five a layer.
# Every expert is held, so the draw moves which experts are busy and not
# how many rows the chip gets (PERF.md section 7 (b))
BIAS_STD = 0.01
# the published renormalisation's epsilon (the program's is 1e-20: the
# configuration's `assumed`)
NORM_EPS_TOPK = 1e-6


# ------------------------------------------------------------ sizes
@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    layer_types: Tuple[str, ...]
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    conv: int                       # the convolution's taps
    d_ff: int
    moe_ff: int
    experts: int
    top_k: int
    dense_layers: int
    use_bias: bool
    norm_topk: bool
    route_scale: float
    norm_eps: float

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def held(self) -> int:
        """Experts of a layer this chip holds: all."""
        return self.experts

    def of_kind(self, kind: str) -> Tuple[int, ...]:
        """The layers of one kind: a mixer's (`"conv"`; `"full_attention"`
        or `"*"`), or `"E"`: those whose feed-forward is routed."""
        if kind == "E":
            return tuple(range(min(self.dense_layers, self.layers),
                               self.layers))
        kind = ATTENTION if kind == "*" else kind
        return tuple(i for i, k in enumerate(self.layer_types) if k == kind)


def sizes(cfg: dict) -> Sizes:
    n = cfg["num_hidden_layers"]
    if len(cfg["layer_types"]) != n:
        raise ValueError(f"layer_types names {len(cfg['layer_types'])} "
                         f"layers, num_hidden_layers {n}")
    if cfg["conv_bias"]:
        raise ValueError("a convolution with a bias is not written here")
    return Sizes(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"]),
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        # (the published file has no key for it; a rehearsal may give one)
        head_dim=(cfg.get("head_dim")
                  or cfg["hidden_size"] // cfg["num_attention_heads"]),
        rope_theta=float(cfg["rope_theta"]), conv=cfg["conv_L_cache"],
        d_ff=cfg["intermediate_size"], moe_ff=cfg["moe_intermediate_size"],
        experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        dense_layers=cfg["num_dense_layers"],
        use_bias=bool(cfg["use_expert_bias"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        norm_eps=float(cfg["norm_eps"]))


def tiny(cfg: dict) -> dict:
    """The same file at rehearsal size: control flow on the CPU, never a
    measurement. Every mechanism stays: one period of both mixers, 4 query
    heads over 2 kv heads, three taps, one dense layer and three of 8
    experts top-2 under the bias, the tied head."""
    small = dict(cfg)
    small.update(hidden_size=64, num_hidden_layers=4,
                 layer_types=list(cfg["layer_types"][:4]),
                 num_attention_heads=4, num_key_value_heads=2,
                 intermediate_size=128, moe_intermediate_size=32,
                 num_experts=8, num_experts_per_tok=2, num_dense_layers=1,
                 vocab_size=512)
    return small


# ------------------------------------------------------------ weights
def weight_shapes(s: Sizes) -> dict:
    """Normal weights of 0.02, output projections scaled down by depth,
    norm scales (the two head norms among them) 0.1 around the identity,
    the convolution's taps 0.5, the router's bias `BIAS_STD`; the per-layer
    layout the program's `GatedConvMoE` holds, and no `lm_head`."""
    e = s.d_model
    std = 0.02
    out_std = std / math.sqrt(2 * s.layers)

    def layer(i):
        if s.layer_types[i] == ATTENTION:
            shapes = {"norm": ((e,), 0.1), "wq": ((e, s.q_dim), std),
                      "wk": ((e, s.kv_dim), std), "wv": ((e, s.kv_dim), std),
                      "wo": ((s.q_dim, e), out_std),
                      "q_norm": ((s.head_dim,), 0.1),
                      "k_norm": ((s.head_dim,), 0.1)}
        else:
            shapes = {"norm": ((e,), 0.1), "w_in": ((e, 3 * e), std),
                      "conv": ((s.conv, e), 0.5),
                      "w_out": ((e, e), out_std)}
        shapes["mlp_norm"] = ((e,), 0.1)
        if i < s.dense_layers:
            shapes.update(gate=((e, s.d_ff), std), up=((e, s.d_ff), std),
                          down=((s.d_ff, e), out_std))
            return shapes
        E, f = s.experts, s.moe_ff
        shapes.update(router=((e, E), std), router_bias=((E,), BIAS_STD),
                      moe_gate=((E, e, f), std), moe_up=((E, e, f), std),
                      moe_down=((E, f, e), out_std))
        return shapes

    return {"embed": ((s.vocab, e), std), "final_norm": ((e,), 0.1),
            "layers": [layer(i) for i in range(s.layers)]}


def param_count(s: Sizes) -> int:
    from benchmarks.harness import weights
    return weights.param_count(weight_shapes(s))


# ------------------------------------------------------------ the program
def program_config(cfg: dict, max_seq_len: int, **extra):
    """The program's GatedConvMoEConfig for this file."""
    from ray_tpu.models.gated_conv_moe import GatedConvMoEConfig
    s = sizes(cfg)
    dtype = cfg.get("torch_dtype", "bfloat16")
    return GatedConvMoEConfig(
        vocab_size=s.vocab, d_model=s.d_model, layer_types=s.layer_types,
        n_heads=s.heads, n_kv_heads=s.kv_heads, head_dim=s.head_dim,
        rope_theta=s.rope_theta, conv_width=s.conv, d_ff=s.d_ff,
        moe_intermediate_size=s.moe_ff, num_experts=s.experts,
        num_experts_per_tok=s.top_k, num_dense_layers=s.dense_layers,
        use_expert_bias=s.use_bias, norm_topk_prob=s.norm_topk,
        routed_scaling_factor=s.route_scale, max_seq_len=max_seq_len,
        norm_eps=s.norm_eps,
        **{"dtype": dtype, "param_dtype": dtype, **extra})


def train_model(cfg: dict, seq_len: int):
    from ray_tpu.models.gated_conv_moe import GatedConvMoE
    return GatedConvMoE(program_config(cfg, max_seq_len=seq_len))


# ------------------------------------------------------------ the reference
def _conv(g, w):
    """g (n, channels), w (taps, channels): `c_t = sum_i w_i g_{t - taps +
    1 + i}`, zeros before the sequence, as shifted sums; linear."""
    n, taps = g.shape[0], w.shape[0]
    padded = jnp.pad(g, ((taps - 1, 0), (0, 0)))
    return sum(w[i] * padded[i:i + n] for i in range(taps))


def _gated_conv(s: Sizes, h, layer, quant):
    """The gated convolution on one sequence: h (n, d_model) f32, normed.
    The gates and the taps are elementwise float32 in both the reference
    and its control; the two projections' operands are the control's."""
    B, C, u = jnp.split(_mm(h, layer["w_in"], quant), 3, axis=-1)
    return _mm(C * _conv(B * u, layer["conv"]), layer["w_out"], quant)


def _attention(s: Sizes, h, layer, quant, remat=False):
    """Grouped-query attention on one sequence: h (n, d_model) f32,
    normed; q and k normed a head, then rotated."""
    n, hd = h.shape[0], s.head_dim
    at = jnp.arange(n)
    q = _mm(h, layer["wq"], quant).reshape(n, s.heads, hd)
    k = _mm(h, layer["wk"], quant).reshape(n, s.kv_heads, hd)
    v = _mm(h, layer["wv"], quant).reshape(n, s.kv_heads, hd)
    q = _rope(_rms(q, layer["q_norm"], s.norm_eps), at, s.rope_theta)
    k = _rope(_rms(k, layer["k_norm"], s.norm_eps), at, s.rope_theta)
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
    return _mm(out.transpose(1, 0, 2).reshape(n, s.q_dim), layer["wo"],
               quant)


def route(s: Sizes, h, layer):
    """(experts (n, k), weights (n, k)) of tokens h (n, d_model), float32
    throughout and never rounded by the control: the choice by score +
    bias, the weights the scores themselves."""
    scores = jax.nn.sigmoid(jnp.matmul(h, layer["router"].astype(F32),
                                       precision=HIGHEST))
    choice = scores
    if s.use_bias:
        choice = scores + layer["router_bias"].astype(F32)
    _, top_e = jax.lax.top_k(choice, s.top_k)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if s.norm_topk:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True)
                         + NORM_EPS_TOPK)
    return top_e, top_w * s.route_scale


def _swiglu(h, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(h, gate, quant)) * _mm(h, up, quant), down,
               quant)


def _experts(s: Sizes, h, layer, quant):
    """sum_e w_e E_e(h): the experts walked one by one, each lifted to
    float32 alone, a token's weight zero for an expert it did not
    choose."""
    n = h.shape[0]
    top_e, top_w = route(s, h, layer)
    weight = jnp.zeros((n, s.experts), F32).at[
        jnp.arange(n)[:, None], top_e].add(top_w)

    def one(acc, ew):
        gate, up, down, w = ew
        y = _swiglu(h, gate.astype(F32), up.astype(F32), down.astype(F32),
                    quant)
        return acc + w[:, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (layer["moe_gate"], layer["moe_up"],
                         layer["moe_down"], weight.T))
    return y


_BIG = ("moe_gate", "moe_up", "moe_down")


def _block(s: Sizes, x, layer, quant, remat=False):
    """A layer on one sequence: x (seq, d_model) f32; its kind is read off
    its leaves. A matrix is lifted to float32 where it is multiplied."""
    small = {k: (v if k in _BIG else v.astype(F32))
             for k, v in layer.items()}
    h = _rms(x, small["norm"], s.norm_eps)
    if "wq" in small:
        x = x + _attention(s, h, small, quant, remat)
    else:
        x = x + _gated_conv(s, h, small, quant)
    h = _rms(x, small["mlp_norm"], s.norm_eps)
    if "router" in small:
        return x + _experts(s, h, small, quant)
    return x + _swiglu(h, small["gate"], small["up"], small["down"], quant)


def _head(s: Sizes, x, norm, table, quant, window=None):
    """The final norm and the tied head, a block of the table's rows at a
    time, each lifted to float32 alone."""
    if window is not None:
        x = jax.lax.dynamic_slice_in_dim(x, window[0], window[1], axis=0)
    x = _rms(x, norm.astype(F32), s.norm_eps)
    vocab = table.shape[0]
    blocks = HEAD_BLOCKS if vocab % HEAD_BLOCKS == 0 else 1
    width = vocab // blocks

    def one(j):
        rows = jax.lax.dynamic_slice_in_dim(table, j * width, width, axis=0)
        return _mm(x, rows.astype(F32).T, quant)

    out = jax.lax.map(one, jnp.arange(blocks))       # (blocks, rows, width)
    return out.transpose(1, 0, 2).reshape(x.shape[0], vocab)


def logits_fn(s: Sizes, params, tokens, quant=_ident, window=None,
              remat=False):
    """tokens (seq,) int32 -> logits (seq, vocab) f32 of one sequence, or
    of the `window` = (start, rows) of its positions."""
    x = params["embed"][tokens].astype(F32)
    for layer in params["layers"]:
        block = functools.partial(_block, s, quant=quant, remat=remat)
        if remat:       # the backward keeps one layer's activations
            block = jax.checkpoint(block)
        x = block(x, layer)
    return _head(s, x, params["final_norm"], params["embed"], quant, window)


def loss_fn(s: Sizes, params, tokens, quant=_ident, remat=False):
    """Mean next-token cross-entropy of one sequence, tokens (seq,)."""
    logits = logits_fn(s, params, tokens, quant, remat=remat)[:-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return -jnp.mean(picked)


_QUANT = {False: _ident, True: fp8_round}
# (a layer's kind is the structure of its tree: four programs)
_jit_block = jax.jit(
    lambda s, x, layer, control: _block(s, x, layer, _QUANT[control]),
    static_argnums=(0, 3))
_jit_head = jax.jit(
    lambda s, x, norm, table, start, rows, control: _head(
        s, x, norm, table, _QUANT[control], (start, rows)),
    static_argnums=(0, 5, 6))


def reference_rows(s: Sizes, params, tokens, start, rows: int,
                   control: bool = False):
    """Logits of positions start .. start + rows - 1 of one sequence that
    is padded at its end (every layer is causal and a token's experts are
    its own, so the padding touches nothing before it). `control` rounds
    every matmul operand to fp8 instead, attention's q, k, v and
    probabilities among them; the gates, the taps and the routing stay
    float32 in both. Run layer by layer (this module's docstring says
    why)."""
    x = params["embed"][tokens].astype(F32)
    for layer in params["layers"]:
        x = _jit_block(s, x, layer, control)
    return _jit_head(s, x, params["final_norm"], params["embed"], start,
                     rows, control)


# ------------------------------------------------------------ required ops
def matmul_params(s: Sizes) -> float:
    """Parameters that multiply a token's activations: a mixer's
    projections, a dense feed-forward or the router and the
    `num_experts_per_tok` experts a token chooses, and the head (the table,
    read once more as a matrix). Not the table's gather, the norms or the
    taps."""
    e = s.d_model
    total = float(e * s.vocab)
    for i, kind in enumerate(s.layer_types):
        total += (2 * e * s.q_dim + 2 * e * s.kv_dim if kind == ATTENTION
                  else 4 * e * e)
        total += (3 * e * s.d_ff if i < s.dense_layers
                  else e * s.experts + s.top_k * 3 * e * s.moe_ff)
    return total


def attention_flops_per_token(s: Sizes, seq_len: int,
                              passes: int = 3) -> float:
    """The mixers' own work per token, all layers: attention's causal QK^T
    and PV (2 x head_dim operations each a head and key seen) in the
    attention layers, the taps' multiply and add and the two gates in the
    others; the backward is twice the forward (`passes` 3)."""
    full = 4.0 * s.head_dim * s.heads * (seq_len + 1) / 2.0
    conv = (2.0 * s.conv + 2.0) * s.d_model
    return passes * (len(s.of_kind(ATTENTION)) * full
                     + len(s.of_kind(CONV)) * conv)


def train_flops_per_token(s: Sizes, seq_len: int) -> float:
    """Forward + backward: 6 per matmul parameter plus the mixers."""
    return 6.0 * matmul_params(s) + attention_flops_per_token(s, seq_len)


def full_decode_call(s: Sizes, live_positions: int, lanes: int,
                     itemsize: int = 2) -> dict:
    """The attention layers' decode attention over `live_positions` cache
    positions a layer (`engine.decode_dispatch`'s, summed over lanes and
    steps): each position's key and value (`kv_dim` = 512 numbers each, 64
    of them a head) read once a layer, each lane's queries in and outputs
    out; QK^T and PV 2 x 64 operations each a query head and position. A
    kernel that multiplies a pair of heads' lanes for one head's scores is
    not credited the pair, nor a page's unused tail."""
    n = len(s.of_kind(ATTENTION))
    return {"flops": n * 4.0 * live_positions * s.q_dim,
            "bytes": float(n * (2 * live_positions * s.kv_dim
                                + 2 * lanes * s.q_dim) * itemsize)}


def flash_prefill_call(s: Sizes, tokens: int, itemsize: int = 2) -> dict:
    """One prefill's flash forward over the attention layers at `tokens`
    true positions: causal QK^T and PV, 2 x head_dim operations each a
    query head and key seen; q, k, v read and the output written once. What
    a padded bucket holds past the prompt and the masked half of a diagonal
    block, which the kernel computes too, do not count."""
    n = len(s.of_kind(ATTENTION))
    keys = tokens * (tokens + 1) / 2.0
    return {"flops": n * 4.0 * s.head_dim * s.heads * keys,
            "bytes": float(n * (2 * tokens * s.q_dim
                                + 2 * tokens * s.kv_dim) * itemsize)}


def moe_gmm_call(s: Sizes, pairs: int, experts_touched: int,
                 itemsize: int = 2) -> dict:
    """The routed experts' three grouped matmuls, as the algorithm needs
    them, `pairs` (token, expert) pairs and `experts_touched` experts with
    at least one pair, both summed over layers and steps: the three
    matrices of each touched expert read once, each pair's activation in
    and result out; 6 * d_model * moe_ff operations a pair. An expert that
    got no pair costs nothing."""
    weights = experts_touched * 3 * s.d_model * s.moe_ff * itemsize
    acts = pairs * 2 * s.d_model * itemsize
    return {"flops": 6.0 * s.d_model * s.moe_ff * pairs,
            "bytes": float(weights + acts)}
