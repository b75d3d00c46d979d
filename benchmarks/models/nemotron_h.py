"""Everything the yardstick knows of one architecture: the decoder whose
layers are one mixer each, a Mamba-2 state-space mixer, a mixture of experts
in a latent, or grouped-query attention, named one by one by a pattern
(`nemotron_h`: NVIDIA-Nemotron-3-Super-120B-A12B, 40 : 40 : 8 of 88).
`benchmarks/models/dense_gqa.py` states the interface this file implements
(`harness/modelcfg.INTERFACE`).

The layers, as the reference computes them (float32, precision `highest`,
nothing of the program imported). Every layer is `x' = x + Mixer(N(x))`, `N`
an RMSNorm of its own (`layer_norm_epsilon`); a final norm, an untied head.
`hybrid_override_pattern` names the mixer: `M`, `E` or `*`.

- `M`, with H = `mamba_num_heads` heads of P = `mamba_head_dim`, G =
  `n_groups`, N = `ssm_state_size`: `[z | xBC | dt] = u W_in` (no bias); a
  causal depthwise convolution of width `conv_kernel` with bias over the
  channels of xBC, written as four shifted sums, then SiLU; `[x | B | C] =
  xBC` (x: H heads of P; B, C: G groups of N; head i reads group i // (H /
  G)); `dt = softplus(dt + dt_bias)`, `a_t = exp(-exp(A_log) dt_t)`, one
  number a head; the recurrence **position by position**, a `lax.scan` over
  the float32 state h (P x N a head): `h = a_t h + dt_t x_t B_t^T`, `y_t =
  h C_t + D x_t`; `y = RMSNorm(y * SiLU(z))` over each group's H P / G
  channels; `out = y W_out`.
- `*`: `q = u W_q` (heads of `head_dim`), `k, v = u W_k, u W_v` (kv heads),
  scores `q_i . k_j / sqrt(head_dim)` for `j <= i`, the mask written out,
  one head at a time; softmax; `W_o`. No bias, no rotary embedding.
- `E`: `s = sigmoid(u W_r)` in float32 over `n_routed_experts` (published:
  512) slots; the top `num_experts_per_tok` of `s + b` chosen (`b`,
  `e_score_correction_bias`, moves the choice only; `n_group` 1, no group
  limit); `w = routed_scaling_factor s / sum of the chosen s`
  (`norm_topk_prob`); `l = u W_fc1` (the latent, `moe_latent_size`);
  `E_i(l) = relu(l W_up_i)^2 W_down_i`, two matrices (`mlp_hidden_act`
  relu2); `MoE(u) = (sum over chosen i of w_i E_i(l)) W_fc2 + relu(u
  S_up)^2 S_down`, the shared expert on the stream.
- **One chip's share**: the configuration holds `n_routed_experts` experts
  of the published count, `deployment.experts_held = [first, last)`. The
  router keeps its published width; the reference, like the program, adds
  the held experts' parts in the latent, then `W_fc2`, then the shared
  expert, and nothing for the experts held elsewhere. The vocabulary is the
  configuration's slice: a smaller vocabulary, for the embedding, the head
  and the traffic alike.

Departures from the published description, none with effect on the
mathematics: norm scales are stored as w with the layer multiplying by
(1 + w), and `A_log`, `dt_bias`, `D` as offsets from the configuration's
`mamba_a_log_init`, `mamba_dt_bias_init`, `mamba_d_init` (the middle of the
family's initialisation), both the program's convention, so one set of
seeded zero-mean weights feeds both and gives decays a trained layer has;
each held expert is computed for every token and weighted by zero where
the token did not choose it, one expert lifted to float32 at a time;
`e_score_correction_bias` is a seeded leaf of std `BIAS_STD`. What
`config.json` leaves to the modelling code is listed in the configuration
file under `assumed`.

`reference_rows` runs each layer as one jitted program, so that only one
layer's matrices are float32 at a time: it has to fit beside 9.3 GB of
served weights and the pools.

`Sizes` holds the published sizes by kind of layer. Of its fields the
harness reads `vocab`; the metrics read this module's `full_decode_call`,
`moe_gmm_call`, `ssd_step_call`, `ssd_chunk_call`, and `held`, `kv_dim`,
`of_kind`.

The weight tree has the program's layout (`ray_tpu/models/
hybrid_ssm_moe.py`): layers held one by one in a list.
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

SSM, EXPERTS, ATTENTION = "M", "E", "*"
HEAD_BLOCKS = 8         # column blocks the output head is multiplied in
# std of the seeded e_score_correction_bias: of 512 sigmoid scores at the
# published widths the 22nd and 23rd largest lie some 0.0025 apart, and a
# tenth of that moves about one token's last choice in twenty. A larger
# one makes whole slots popular, and which of the 128 held slots drew what
# then sets how many experts a step reads (PERF.md section 7 (b))
BIAS_STD = 0.00025


# ------------------------------------------------------------ sizes
@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    pattern: Tuple[str, ...]        # a letter a layer: M, E or *
    heads: int                      # an attention layer's query heads
    kv_heads: int
    head_dim: int
    m_heads: int                    # a Mamba layer's heads
    m_head_dim: int
    groups: int                     # groups of heads that share B and C
    state: int                      # N: a channel's state
    conv: int                       # the convolution's width
    chunk: int                      # positions a prefill chunk
    a_log_init: float
    dt_bias_init: float
    d_init: float
    latent: int                     # the experts' input and output width
    moe_ff: int
    shared_ff: int
    experts: int                    # of the whole layer, as published
    first_held: int
    held: int                       # experts this chip holds
    top_k: int
    route_scale: float
    norm_eps: float

    @property
    def layers(self) -> int:
        return len(self.pattern)

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def d_inner(self) -> int:       # a Mamba mixer's width
        return self.m_heads * self.m_head_dim

    @property
    def bc_dim(self) -> int:
        return self.groups * self.state

    @property
    def channels(self) -> int:      # what the convolution runs over
        return self.d_inner + 2 * self.bc_dim

    def of_kind(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.pattern) if k == kind)


def sizes(cfg: dict) -> Sizes:
    pattern = tuple(cfg["hybrid_override_pattern"])
    if len(pattern) != cfg["num_hidden_layers"] or set(pattern) - {
            SSM, EXPERTS, ATTENTION}:
        raise ValueError(f"hybrid_override_pattern {''.join(pattern)!r} "
                         f"does not name num_hidden_layers "
                         f"{cfg['num_hidden_layers']} layers of M, E, *")
    for key, want in (("mlp_hidden_act", "relu2"), ("n_group", 1),
                      ("topk_group", 1), ("n_shared_experts", 1),
                      ("use_conv_bias", True), ("mamba_proj_bias", False),
                      ("norm_topk_prob", True),
                      ("num_nextn_predict_layers", 0)):
        if cfg[key] != want:
            raise ValueError(f"{key} {cfg[key]!r}: only {want!r} is "
                             f"written down here")
    held = cfg["n_routed_experts"]
    first, last = cfg["deployment"]["experts_held"]
    if last - first != held:
        raise ValueError(f"deployment.experts_held {[first, last]} is not "
                         f"the {held} experts of n_routed_experts")
    return Sizes(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        pattern=pattern, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        m_heads=cfg["mamba_num_heads"], m_head_dim=cfg["mamba_head_dim"],
        groups=cfg["n_groups"], state=cfg["ssm_state_size"],
        conv=cfg["conv_kernel"], chunk=cfg["chunk_size"],
        a_log_init=float(cfg["mamba_a_log_init"]),
        dt_bias_init=float(cfg["mamba_dt_bias_init"]),
        d_init=float(cfg["mamba_d_init"]),
        latent=cfg["moe_latent_size"], moe_ff=cfg["moe_intermediate_size"],
        shared_ff=cfg["moe_shared_expert_intermediate_size"],
        experts=cfg.get("published", {}).get("n_routed_experts", held),
        first_held=first, held=held, top_k=cfg["num_experts_per_tok"],
        route_scale=float(cfg["routed_scaling_factor"]),
        norm_eps=float(cfg["layer_norm_epsilon"]))


def tiny(cfg: dict) -> dict:
    """The same file at rehearsal size: control flow on the CPU, never a
    measurement. Every mechanism stays: all three kinds of layer (two Mamba,
    two expert, one attention), 4 Mamba heads of 8 in 2 groups with a state
    of 16, chunks of 8, 4 query heads over 2 kv heads, a latent of half the
    stream, a share of 4 of 16 experts that does not start at 0."""
    small = dict(cfg)
    small.update(hidden_size=64, num_hidden_layers=5,
                 hybrid_override_pattern="MEM*E", num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, mamba_num_heads=4,
                 mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                 chunk_size=8, moe_latent_size=32, moe_intermediate_size=48,
                 moe_shared_expert_intermediate_size=96, n_routed_experts=4,
                 num_experts_per_tok=4, vocab_size=512,
                 published={**cfg.get("published", {}),
                            "n_routed_experts": 16},
                 deployment={**cfg["deployment"], "experts_held": [4, 8]})
    return small


# ------------------------------------------------------------ weights
def weight_shapes(s: Sizes) -> dict:
    """Normal weights of 0.02, output projections scaled down by depth, norm
    scales 0.1 around the identity; the convolution's taps 0.5 (they pass
    their input at about its size) and its bias 0.1; `a_log` 0.7 and
    `dt_bias` 1.0 around the configuration's initial values, the spread of
    the family's own initialisation (A uniform in (1, 16), the step
    log-uniform in (0.001, 0.1)), `d` 0.1 around 1, so that the heads'
    decays differ as a trained layer's do; the router's bias `BIAS_STD`.
    The experts' second matrix is 0.03: a squared ReLU behind two more
    projections than the shared expert has would else add a tenth of what
    the shared expert adds; with it the held experts add about a sixth. No
    more, because the check reads every choice that bfloat16 makes
    otherwise than float32, and of 22 near-equal choices the last changes
    in a third of the tokens (PERF.md section 6, PR 46): with `fc2` not
    scaled by depth, three times this, the sound runs read 0.09-0.19
    beside a control of 0.37.
    The per-layer layout the program's `HybridSSMMoE` holds, the held
    experts alone."""
    e = s.d_model
    std = 0.02
    out_std = std / math.sqrt(s.layers)

    def layer(kind):
        if kind == ATTENTION:
            return {"norm": ((e,), 0.1), "wq": ((e, s.q_dim), std),
                    "wk": ((e, s.kv_dim), std), "wv": ((e, s.kv_dim), std),
                    "wo": ((s.q_dim, e), out_std)}
        if kind == EXPERTS:
            return {"norm": ((e,), 0.1), "router": ((e, s.experts), std),
                    "router_bias": ((s.experts,), BIAS_STD),
                    "fc1": ((e, s.latent), std),
                    "fc2": ((s.latent, e), out_std),
                    "moe_up": ((s.held, s.latent, s.moe_ff), std),
                    "moe_down": ((s.held, s.moe_ff, s.latent), 1.5 * std),
                    "shared_up": ((e, s.shared_ff), std),
                    "shared_down": ((s.shared_ff, e), out_std)}
        H = s.m_heads
        return {"norm": ((e,), 0.1),
                "w_in": ((e, s.d_inner + s.channels + H), std),
                "conv": ((s.conv, s.channels), 0.5),
                "conv_bias": ((s.channels,), 0.1),
                "a_log": ((H,), 0.7), "dt_bias": ((H,), 1.0),
                "d": ((H,), 0.1), "gate_norm": ((s.d_inner,), 0.1),
                "w_out": ((s.d_inner, e), out_std)}

    return {"embed": ((s.vocab, e), std), "final_norm": ((e,), 0.1),
            "lm_head": ((e, s.vocab), std),
            "layers": [layer(k) for k in s.pattern]}


def param_count(s: Sizes) -> int:
    from benchmarks.harness import weights
    return weights.param_count(weight_shapes(s))


# ------------------------------------------------------------ the program
def program_config(cfg: dict, max_seq_len: int, **extra):
    """The program's HybridSSMMoEConfig for this file."""
    from ray_tpu.models.hybrid_ssm_moe import HybridSSMMoEConfig
    s = sizes(cfg)
    dtype = cfg.get("torch_dtype", "bfloat16")
    return HybridSSMMoEConfig(
        vocab_size=s.vocab, d_model=s.d_model, layer_types=s.pattern,
        n_heads=s.heads, n_kv_heads=s.kv_heads, head_dim=s.head_dim,
        ssm_heads=s.m_heads, ssm_head_dim=s.m_head_dim,
        ssm_groups=s.groups, ssm_state=s.state, conv_width=s.conv,
        chunk=s.chunk, a_log_init=s.a_log_init,
        dt_bias_init=s.dt_bias_init, d_init=s.d_init,
        moe_latent_size=s.latent, moe_intermediate_size=s.moe_ff,
        shared_intermediate_size=s.shared_ff, n_routed_experts=s.experts,
        experts_held=(s.first_held, s.held), num_experts_per_tok=s.top_k,
        routed_scaling_factor=s.route_scale, max_seq_len=max_seq_len,
        norm_eps=s.norm_eps,
        **{"dtype": dtype, "param_dtype": dtype, **extra})


def train_model(cfg: dict, seq_len: int):
    from ray_tpu.models.hybrid_ssm_moe import HybridSSMMoE
    return HybridSSMMoE(program_config(cfg, max_seq_len=seq_len))


# ------------------------------------------------------------ the reference
def _lift(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


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


def _mamba(s: Sizes, u, layer, quant):
    """A Mamba-2 mixer on one sequence: u (n, d_model) f32, normed."""
    n, H, G = u.shape[0], s.m_heads, s.groups
    z, xbc, dt = jnp.split(_mm(u, layer["w_in"], quant),
                           [s.d_inner, s.d_inner + s.channels], axis=-1)
    xbc = _conv_silu(xbc, layer["conv"], layer["conv_bias"])
    x, Bm, Cm = jnp.split(xbc, [s.d_inner, s.d_inner + s.bc_dim], axis=-1)
    x = x.reshape(n, H, s.m_head_dim)
    dt = jax.nn.softplus(dt + s.dt_bias_init + layer["dt_bias"])
    A = jnp.exp(s.a_log_init + layer["a_log"])
    y = _scan(s, quant(x), quant(Bm.reshape(n, G, s.state)),
              quant(Cm.reshape(n, G, s.state)), dt, A)
    y = y + (s.d_init + layer["d"])[:, None] * x
    y = y.reshape(n, s.d_inner) * jax.nn.silu(z)
    y = _rms(y.reshape(n, G, -1), layer["gate_norm"].reshape(G, -1),
             s.norm_eps)
    return _mm(y.reshape(n, s.d_inner), layer["w_out"], quant)


def _attention(s: Sizes, u, layer, quant, remat=False):
    """Grouped-query attention on one sequence: u (n, d_model) f32."""
    n, hd = u.shape[0], s.head_dim
    q = _mm(u, layer["wq"], quant).reshape(n, s.heads, hd)
    k = _mm(u, layer["wk"], quant).reshape(n, s.kv_heads, hd)
    v = _mm(u, layer["wv"], quant).reshape(n, s.kv_heads, hd)
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
    return _mm(out.transpose(1, 0, 2).reshape(n, s.q_dim), layer["wo"],
               quant)


def route(s: Sizes, u, layer):
    """(slots (n, k), weights (n, k)) of tokens u (n, d_model), float32
    throughout and never rounded by the control: a sigmoid a slot, the bias
    moves the choice, the weights are the chosen scores over their sum
    times `route_scale`."""
    scores = jax.nn.sigmoid(jnp.matmul(u, layer["router"].astype(F32),
                                       precision=HIGHEST))
    _, top_e = jax.lax.top_k(scores + layer["router_bias"].astype(F32),
                             s.top_k)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    return top_e, s.route_scale * top_w / (
        jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)


def slot_weights(s: Sizes, u, layer):
    """(n, experts) float32: a token's weight at each slot it chose, zero
    elsewhere."""
    top_e, top_w = route(s, u, layer)
    n = u.shape[0]
    return jnp.zeros((n, s.experts), F32).at[
        jnp.arange(n)[:, None], top_e].add(top_w)


def _relu2(h, up, down, quant):
    return _mm(jnp.square(jax.nn.relu(_mm(h, up, quant))), down, quant)


def held_part(s: Sizes, u, layer, quant):
    """This share of the routed experts, back in the stream: the held
    experts walked one by one on the latent, each lifted to float32 alone,
    a token's weight zero for an expert it did not choose, summed in the
    latent; then `W_fc2`. Nothing for the experts held elsewhere."""
    mine = slot_weights(s, u, layer)[:, s.first_held:s.first_held + s.held]
    latent = _mm(u, layer["fc1"].astype(F32), quant)

    def one(acc, ew):
        up, down, w = ew
        return acc + w[:, None] * _relu2(latent, up.astype(F32),
                                         down.astype(F32), quant), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(latent),
                          (layer["moe_up"], layer["moe_down"], mine.T))
    return _mm(acc, layer["fc2"].astype(F32), quant)


def shared_part(s: Sizes, u, layer, quant):
    """The shared expert, which every chip computes alike."""
    return _relu2(u, layer["shared_up"].astype(F32),
                  layer["shared_down"].astype(F32), quant)


def _experts(s: Sizes, u, layer, quant):
    return held_part(s, u, layer, quant) + shared_part(s, u, layer, quant)


def _block(s: Sizes, kind: str, x, layer, quant, remat=False):
    """A layer of `kind` on one sequence: x (seq, d_model) f32. The
    experts' matrices are lifted one at a time inside; every other leaf
    here."""
    if kind == EXPERTS:
        u = _rms(x, layer["norm"].astype(F32), s.norm_eps)
        return x + _experts(s, u, layer, quant)
    layer = _lift(layer)
    u = _rms(x, layer["norm"], s.norm_eps)
    if kind == ATTENTION:
        return x + _attention(s, u, layer, quant, remat)
    return x + _mamba(s, u, layer, quant)


def _head(s: Sizes, x, norm, w, quant, window=None):
    """The final norm and the head, a block of columns at a time, each
    lifted to float32 alone."""
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
    return out.transpose(1, 0, 2).reshape(x.shape[0], vocab)


def logits_fn(s: Sizes, params, tokens, quant=_ident, window=None,
              remat=False):
    """tokens (seq,) int32 -> logits (seq, vocab) f32 of one sequence, or
    of the `window` = (start, rows) of its positions."""
    x = params["embed"].astype(F32)[tokens]
    for kind, layer in zip(s.pattern, params["layers"]):
        block = functools.partial(_block, s, kind, quant=quant, remat=remat)
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
    lambda s, kind, x, layer, control: _block(s, kind, x, layer,
                                              _QUANT[control]),
    static_argnums=(0, 1, 4))
_jit_head = jax.jit(
    lambda s, x, norm, head, start, rows, control: _head(
        s, x, norm, head, _QUANT[control], (start, rows)),
    static_argnums=(0, 5, 6))


def reference_rows(s: Sizes, params, tokens, start, rows: int,
                   control: bool = False):
    """Logits of positions start .. start + rows - 1 of one sequence that
    is padded at its end (every layer is causal, and a token's experts are
    its own, so the padding touches nothing before it). `control` rounds
    every matmul operand to fp8 instead, the scan's x, B and C among them;
    the routing, the decays, dt and the state stay float32 in both. One
    jitted program a kind of layer, run layer by layer (this module's
    docstring says why)."""
    x = params["embed"][tokens].astype(F32)
    for kind, layer in zip(s.pattern, params["layers"]):
        x = _jit_block(s, kind, x, layer, control)
    return _jit_head(s, x, params["final_norm"], params["lm_head"], start,
                     rows, control)


# ------------------------------------------------------------ required ops
def _mixer_params(s: Sizes, kind: str) -> float:
    if kind == ATTENTION:
        return 2 * s.d_model * s.q_dim + 2 * s.d_model * s.kv_dim
    if kind == EXPERTS:
        return (s.d_model * s.experts + 2 * s.d_model * s.latent
                + 2 * s.d_model * s.shared_ff
                + s.top_k * s.held / s.experts * 2 * s.latent * s.moe_ff)
    return (s.d_model * (s.d_inner + s.channels + s.m_heads)
            + s.d_inner * s.d_model)


def matmul_params(s: Sizes) -> float:
    """Parameters that multiply a token's activations on this chip: every
    layer's projections, the router at its whole width, the latent's two
    projections and the shared expert, and of the experts the `top_k *
    held / experts` a token's choices give this share when the routing is
    even (5.5 experts a layer at the published sizes); the output head.
    Not the embedding table, the norms, the convolution's taps or the
    scan's constants."""
    return (sum(_mixer_params(s, k) for k in s.pattern)
            + s.d_model * s.vocab)


def _scan_flops(s: Sizes) -> float:
    """One position of one Mamba layer, position by position: the decay,
    the outer product and its add, `h C` (a multiply and an add), each over
    a layer's d_inner x N."""
    return 5.0 * s.d_inner * s.state


def attention_flops_per_token(s: Sizes, seq_len: int,
                              passes: int = 3) -> float:
    """The mixers' own work per token, all layers: an attention layer's
    causal QK^T and PV (2 x head_dim operations each a head and key seen),
    a Mamba layer's recurrence; the backward is twice the forward (`passes`
    3)."""
    full = 4.0 * s.head_dim * s.heads * (seq_len + 1) / 2.0
    return passes * (len(s.of_kind(ATTENTION)) * full
                     + len(s.of_kind(SSM)) * _scan_flops(s))


def train_flops_per_token(s: Sizes, seq_len: int) -> float:
    """Forward + backward: 6 per matmul parameter plus the mixers."""
    return 6.0 * matmul_params(s) + attention_flops_per_token(s, seq_len)


def full_decode_call(s: Sizes, live_positions: int, lanes: int,
                     itemsize: int = 2) -> dict:
    """The attention layers' decode attention over `live_positions` cache
    positions a layer (`engine.decode_dispatch`'s, summed over lanes and
    steps): each position's key and value read once a layer, each lane's
    queries in and outputs out; QK^T and PV 2 x head_dim operations each a
    query head and position. A page's unused tail, which the kernel copies
    too, does not count."""
    n = len(s.of_kind(ATTENTION))
    return {"flops": n * 4.0 * live_positions * s.q_dim,
            "bytes": float(n * (2 * live_positions * s.kv_dim
                                + 2 * lanes * s.q_dim) * itemsize)}


def ssd_step_call(s: Sizes, state_slots: int, itemsize: int = 2) -> dict:
    """The Mamba layers' decode recurrence for `state_slots` lane-steps
    (`engine.decode_dispatch`'s `state_slots`, summed over steps), as the
    kernel's events hold it: a layer's float32 state read and written, x,
    B and C and a step a head in, the float32 outputs out. Bytes bound it.
    The gate z, the skip and the norm are applied outside the kernel's
    events and are not counted, nor is the convolution's tail, which is
    gathered and scattered beside it (`cache.state_bytes_share.agent8k`
    counts it)."""
    n = len(s.of_kind(SSM))
    state = s.state * s.d_inner * 4
    io = s.channels * itemsize + s.m_heads * 4 + s.d_inner * 4
    return {"flops": n * state_slots * _scan_flops(s),
            "bytes": float(n * state_slots * (2 * state + io))}


def ssd_chunk_call(s: Sizes, tokens: int, itemsize: int = 2) -> dict:
    """One prefill's chunked scan over all Mamba layers at `tokens` true
    positions, chunks of C = `chunk`, as the algorithm needs it a chunk:
    the lower triangle of `C B^T` once a group (C^2 N), a head's masked
    triangle times its x (C^2 P), and its two products with the state, `C
    h_0^T` and `X^T B` (2 C N P each); x, B, C and the steps read, the
    outputs written, the last state written once. What a padded bucket
    holds past the prompt is skipped or masked: the program's cost."""
    n, C = len(s.of_kind(SSM)), s.chunk
    per_chunk = (s.groups * C * C * s.state + s.m_heads * (
        C * C * s.m_head_dim + 4.0 * C * s.state * s.m_head_dim))
    nbytes = (tokens * ((s.channels + s.d_inner) * itemsize + s.m_heads * 4)
              + s.state * s.d_inner * 4)
    return {"flops": n * tokens / float(C) * per_chunk,
            "bytes": float(n * nbytes)}


def moe_gmm_call(s: Sizes, pairs: int, experts_touched: int,
                 itemsize: int = 2) -> dict:
    """The held experts' two grouped matmuls, as the algorithm needs them,
    `pairs` (token, held expert) pairs and `experts_touched` held experts
    with at least one pair, both summed over layers and steps: the two
    matrices of each touched expert read once, each pair's latent in and
    result out; 4 * latent * moe_ff operations a pair. An expert that got
    no pair and an expert held elsewhere cost nothing."""
    weights = experts_touched * 2 * s.latent * s.moe_ff * itemsize
    acts = pairs * 2 * s.latent * itemsize
    return {"flops": 4.0 * s.latent * s.moe_ff * pairs,
            "bytes": float(weights + acts)}
