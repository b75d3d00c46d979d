"""Everything the yardstick knows of one architecture: the decoder whose
mixers are delta-rule layers with a decay a head and key channel (KDA) or
latent attention (MLA), one latent layer closing every `layer_group_size`,
over dense or routed feed-forwards whose experts are chosen under a group
limit (`bailing_hybrid`: the language model of Ling-3.0-flash-VL, 35 : 7 of
42). `benchmarks/models/dense_gqa.py` states the interface this file
implements (`harness/modelcfg.INTERFACE`).

The layers, as the reference computes them (float32, precision `highest`,
nothing of the program imported), x (T, hidden) a layer's input. A block is
`x <- x + Mixer(N(x))`, then `x <- x + FFN(N(x))`, `N` an RMSNorm
(`rms_norm_eps`); a final norm, an untied head. Published layer `l` is
latent iff `(l + 1) % layer_group_size == 0`, dense iff `l <
first_k_dense_replace` (published: 2); the file's `deployment.layers_held`
names the published layers that are held.

- KDA mixer, H = `num_attention_heads` heads of key and value width
  `head_dim`: `[q~ | k~ | v~] = u W_qkv` (three matrices side by side); a
  causal depthwise convolution of width `short_conv_kernel_size` over
  those channels, no bias, written as four shifted sums, then SiLU
  (`linear_silu`); a head's `q <- q / |q| / sqrt(dk)`, `k <- k / |k|`
  (`use_qk_norm`); `f = u W_f` (hidden -> H x dk in one matrix:
  `no_kda_lora`); `g = kda_lower_bound sigmoid(exp(A_log) (f + dt_bias))`,
  a number a head and key channel in (`kda_lower_bound`, 0)
  (`kda_safe_gate`); `beta = sigmoid(u W_b)` a head; the recurrence
  **position by position**, a `lax.scan` over the float32 state S (dk x dv
  a head): `S' = Diag(exp(g_t)) S`, `S = S' + beta_t k_t (v_t - S'^T
  k_t)^T`, `o_t = S^T q_t`; `y = (RMSNorm_dv(o) * sigmoid(u W_g)) W_o`, the
  norm a head over its dv (`group_norm_size` 1).
- latent mixer: `q = u W_q` (heads of `qk_nope_head_dim +
  qk_rope_head_dim`, one matrix: `q_lora_rank` null); `[c_kv | k_rope] = u
  W_kva`, `c_kv = RMSNorm(c_kv)`; RoPE (`rope_theta`) on the rope parts;
  `[k_nope | v] = c_kv W_kvb` a head; scores `q . [k_nope | k_rope] /
  sqrt(nope + rope)` for `j <= i`, the mask written out, one head at a
  time, expanded with no cache; softmax; a head's output times
  `sigmoid((u W_a)_h)` (`gated_attention_proj_granularity_type`
  head_wise); `W_o`.
- experts: `s = sigmoid(u W_r)` in float32 over `num_experts` (published:
  512) slots; the choice on `s + expert_bias`: `n_group` groups of
  consecutive slots, a group's score the sum of its two largest `s + bias`,
  the `topk_group` best groups kept, the `num_experts_per_tok` best slots
  among them; `w = routed_scaling_factor s / sum of the chosen s`
  (`norm_topk_prob`); `MoE(u) = sum over chosen i of w_i E_i(u) +
  E_shared(u)`, every expert a SwiGLU.
- **One chip's share**: the configuration holds `num_experts` experts of
  the published count, `deployment.experts_held = [first, last)`. The
  router keeps its published width and its groups; the reference, like the
  program, adds the held experts' parts, then the shared expert, and
  nothing for the experts held elsewhere. The vocabulary is the
  configuration's slice.

Departures from the published description, none with effect on the
mathematics: norm scales are stored as w with the layer multiplying by
(1 + w), and `A_log`, `dt_bias` as offsets from the configuration's
`kda_a_log_init`, `kda_dt_bias_init`, both the program's convention, so
one set of seeded zero-mean weights feeds both; each held expert is
computed for every token and weighted by zero where the token did not
choose it, one expert lifted to float32 at a time; `expert_bias` is a
seeded leaf of std `BIAS_STD`. What `config.json` leaves to the modelling
code is listed in the configuration file under `assumed`.

`reference_rows` runs each layer as one jitted program, so that only one
layer's matrices are float32 at a time beside the served weights and pools.

`Sizes` holds the published sizes by kind of layer. Of its fields the
harness reads `vocab`; the metrics read this module's `kda_step_call`,
`kda_chunk_call`, `mla_decode_call`, `flash_prefill_call`, `moe_gmm_call`,
and `held`, `layers`, `attentions`, `cache_row`, `of_kind` (a layer is of
its mixer's kind and of its feed-forward's: `E` is the letter the
`moe.*.agent8k` readers count expert layers by).

The weight tree has the program's layout (`ray_tpu/models/
hybrid_kda_moe.py`): layers held one by one in a list.
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

LINEAR, LATENT = "linear_attention", "latent_attention"
DENSE, EXPERTS = "dense", "E"
HEAD_BLOCKS = 8         # column blocks the output head is multiplied in
# std of the seeded expert_bias: of the 256 sigmoid scores in the four kept
# groups at the published widths the eighth and ninth largest lie some
# 0.006 apart (the configuration's `assumed` has the measurement), and a
# tenth of that moves about one token's last choice in twenty. A larger one
# makes whole slots popular, and which of the 128 held slots drew what then
# sets how many experts a step reads (PERF.md section 7 (b))
BIAS_STD = 0.0006


# ------------------------------------------------------------ sizes
@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    layer_types: Tuple[str, ...]    # a held layer's mixer
    mlp_types: Tuple[str, ...]      # and its feed-forward: dense or E
    heads: int                      # of both mixers
    dk: int                         # a KDA head's key width
    dv: int
    conv: int                       # the convolution's width
    chunk: int                      # positions a prefill chunk
    lower_bound: float              # of a position's log decay
    a_log_init: float
    dt_bias_init: float
    kv_lora: int
    nope: int
    rope: int
    v_head: int
    d_ff: int
    moe_ff: int
    shared_ff: int
    experts: int                    # of the whole layer, as published
    first_held: int
    held: int                       # experts this chip holds
    top_k: int
    n_group: int
    topk_group: int
    route_scale: float
    rope_theta: float
    norm_eps: float

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    def of_kind(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, kinds in enumerate(zip(
            self.layer_types, self.mlp_types)) if kind in kinds)

    @property
    def attentions(self) -> int:
        """Rows of the latent pool: one a latent layer."""
        return len(self.of_kind(LATENT))

    @property
    def qk_head(self) -> int:
        return self.nope + self.rope

    @property
    def cache_row(self) -> int:
        """Numbers a position costs a latent layer in the cache."""
        return self.kv_lora + self.rope

    @property
    def key_dim(self) -> int:
        return self.heads * self.dk

    @property
    def value_dim(self) -> int:
        return self.heads * self.dv

    @property
    def channels(self) -> int:      # what the convolution runs over
        return 2 * self.key_dim + self.value_dim


# what this file writes down, key by key: another value is refused
_WRITTEN = (("q_lora_rank", None), ("score_function", "sigmoid"),
            ("use_qk_norm", True), ("linear_silu", True),
            ("no_kda_lora", True), ("use_kda_lora", False),
            ("kda_safe_gate", True), ("num_kv_heads_for_linear_attn", 0),
            ("group_norm_size", 1), ("use_mla_nope", False),
            ("gated_attention_proj_granularity_type", "head_wise"),
            ("moe_router_enable_expert_bias", True),
            ("norm_topk_prob", True), ("use_nGPT", False),
            ("scale_router_input", False), ("value_norm", False),
            ("up_proj_norm", False))


def sizes(cfg: dict) -> Sizes:
    for key, want in _WRITTEN:
        if cfg[key] != want:
            raise ValueError(f"{key} {cfg[key]!r}: only {want!r} is "
                             f"written down here")
    if cfg["rotary_dim"] != cfg["qk_rope_head_dim"]:
        raise ValueError("rotary_dim is not qk_rope_head_dim")
    held_layers = tuple(cfg["deployment"]["layers_held"])
    if len(held_layers) != cfg["num_hidden_layers"]:
        raise ValueError(f"deployment.layers_held names "
                         f"{len(held_layers)} layers, num_hidden_layers "
                         f"{cfg['num_hidden_layers']}")
    published = cfg.get("published", {})
    dense_below = published.get("first_k_dense_replace",
                                cfg["first_k_dense_replace"])
    mlp_types = tuple(DENSE if l < dense_below else EXPERTS
                      for l in held_layers)
    if mlp_types.count(DENSE) != cfg["first_k_dense_replace"]:
        raise ValueError("first_k_dense_replace does not count the dense "
                         "layers of deployment.layers_held")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if len(cfg[key]) != len(held_layers) or any(cfg[key]):
            raise ValueError(f"{key}: a clamped SwiGLU is not written "
                             f"down here; hold layers whose limit is 0")
    held = cfg["num_experts"]
    first, last = cfg["deployment"]["experts_held"]
    if last - first != held:
        raise ValueError(f"deployment.experts_held {[first, last]} is not "
                         f"the {held} experts of num_experts")
    return Sizes(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_types=tuple(
            LATENT if (l + 1) % cfg["layer_group_size"] == 0 else LINEAR
            for l in held_layers),
        mlp_types=mlp_types, heads=cfg["num_attention_heads"],
        dk=cfg["head_dim"], dv=cfg["head_dim"],
        conv=cfg["short_conv_kernel_size"],
        chunk=int(cfg["kda_chunk_size"]),
        lower_bound=float(cfg["kda_lower_bound"]),
        a_log_init=float(cfg["kda_a_log_init"]),
        dt_bias_init=float(cfg["kda_dt_bias_init"]),
        kv_lora=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v_head=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"], moe_ff=cfg["moe_intermediate_size"],
        shared_ff=cfg["moe_shared_expert_intermediate_size"],
        experts=published.get("num_experts", held), first_held=first,
        held=held, top_k=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        route_scale=float(cfg["routed_scaling_factor"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]))


def tiny(cfg: dict) -> dict:
    """The same file at rehearsal size: control flow on the CPU, never a
    measurement. Every mechanism stays: a dense KDA layer and one period of
    three (two KDA, one latent) over experts, 4 heads of 8, chunks of 8, a
    latent row of 128, 16 experts in 4 groups of which 2 are kept, a share
    of 4 that does not start at 0."""
    small = dict(cfg)
    small.update(hidden_size=64, num_hidden_layers=4, layer_group_size=3,
                 first_k_dense_replace=1, num_attention_heads=4,
                 num_key_value_heads=4, head_dim=8, kda_chunk_size=8,
                 kv_lora_rank=96, qk_nope_head_dim=16, qk_rope_head_dim=16,
                 rotary_dim=16, v_head_dim=32, intermediate_size=128,
                 moe_intermediate_size=32,
                 moe_shared_expert_intermediate_size=32, num_experts=4,
                 num_experts_per_tok=4, n_group=4, topk_group=2,
                 vocab_size=512,
                 expert_swiglu_limit_list=[0] * 4,
                 share_expert_swiglu_limit_list=[0] * 4,
                 published={**cfg.get("published", {}), "num_experts": 16,
                            "first_k_dense_replace": 1},
                 deployment={**cfg["deployment"], "experts_held": [4, 8],
                             "layers_held": [0, 3, 4, 5]})
    return small


# ------------------------------------------------------------ weights
def weight_shapes(s: Sizes) -> dict:
    """Normal weights of 0.02, output projections scaled down by depth (two
    residual additions a layer), norm scales 0.1 around the identity; the
    convolution's taps 0.5 (they pass their input at about its size);
    `a_log` 0.5 and `dt_bias` 1.0 around the configuration's initial
    values, so that the channels' decays differ as a trained layer's do;
    the router's bias `BIAS_STD`. The per-layer layout the program's
    `HybridKDAMoE` holds, the held experts alone."""
    e, H = s.d_model, s.heads
    std = 0.02
    out_std = std / math.sqrt(2 * s.layers)

    def layer(kind, mlp):
        if kind == LATENT:
            mixer = {"wq": ((e, H * s.qk_head), std),
                     "wkv_a": ((e, s.kv_lora + s.rope), std),
                     "kv_norm": ((s.kv_lora,), 0.1),
                     "wkv_b": ((s.kv_lora, H * (s.nope + s.v_head)), std),
                     "wo": ((H * s.v_head, e), out_std),
                     "w_head_gate": ((e, H), std)}
        else:
            mixer = {"w_qkv": ((e, s.channels), std),
                     "w_f": ((e, s.key_dim), std), "w_b": ((e, H), std),
                     "w_g": ((e, s.value_dim), std),
                     "conv": ((s.conv, s.channels), 0.5),
                     "a_log": ((H,), 0.5), "dt_bias": ((H, s.dk), 1.0),
                     "o_norm": ((s.dv,), 0.1),
                     "wo": ((s.value_dim, e), out_std)}
        if mlp == DENSE:
            ffn = {"gate": ((e, s.d_ff), std), "up": ((e, s.d_ff), std),
                   "down": ((s.d_ff, e), out_std)}
        else:
            E, f, fs = s.held, s.moe_ff, s.shared_ff
            ffn = {"router": ((e, s.experts), std),
                   "router_bias": ((s.experts,), BIAS_STD),
                   "moe_gate": ((E, e, f), std), "moe_up": ((E, e, f), std),
                   "moe_down": ((E, f, e), out_std),
                   "shared_gate": ((e, fs), std),
                   "shared_up": ((e, fs), std),
                   "shared_down": ((fs, e), out_std)}
        return {"attn_norm": ((e,), 0.1), "mlp_norm": ((e,), 0.1),
                **mixer, **ffn}

    return {"embed": ((s.vocab, e), std), "final_norm": ((e,), 0.1),
            "lm_head": ((e, s.vocab), std),
            "layers": [layer(k, m)
                       for k, m in zip(s.layer_types, s.mlp_types)]}


def param_count(s: Sizes) -> int:
    from benchmarks.harness import weights
    return weights.param_count(weight_shapes(s))


# ------------------------------------------------------------ the program
def program_config(cfg: dict, max_seq_len: int, **extra):
    """The program's HybridKDAMoEConfig for this file."""
    from ray_tpu.models.hybrid_kda_moe import HybridKDAMoEConfig
    s = sizes(cfg)
    dtype = cfg.get("torch_dtype", "bfloat16")
    return HybridKDAMoEConfig(
        vocab_size=s.vocab, d_model=s.d_model, layer_types=s.layer_types,
        mlp_layer_types=tuple("dense" if m == DENSE else "sparse"
                              for m in s.mlp_types),
        n_heads=s.heads, linear_key_dim=s.dk, linear_value_dim=s.dv,
        conv_width=s.conv, kda_lower_bound=s.lower_bound, chunk=s.chunk,
        a_log_init=s.a_log_init, dt_bias_init=s.dt_bias_init,
        q_lora_rank=None, kv_lora_rank=s.kv_lora, qk_nope_head_dim=s.nope,
        qk_rope_head_dim=s.rope, v_head_dim=s.v_head, head_gate=True,
        d_ff=s.d_ff, moe_intermediate_size=s.moe_ff,
        shared_intermediate_size=s.shared_ff, n_routed_experts=s.experts,
        experts_held=(s.first_held, s.held), num_experts_per_tok=s.top_k,
        n_group=s.n_group, topk_group=s.topk_group,
        routed_scaling_factor=s.route_scale, norm_topk_prob=True,
        max_seq_len=max_seq_len, rope_theta=s.rope_theta,
        norm_eps=s.norm_eps,
        **{"dtype": dtype, "param_dtype": dtype, **extra})


def train_model(cfg: dict, seq_len: int):
    from ray_tpu.models.hybrid_kda_moe import HybridKDAMoE
    return HybridKDAMoE(program_config(cfg, max_seq_len=seq_len))


# ------------------------------------------------------------ the reference
def _lift(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _conv_silu(x, w):
    """x (n, channels), w (width, channels): `y_t = silu(sum_i w_i x_{t -
    width + 1 + i})`, zeros before the sequence, as shifted sums."""
    n, width = x.shape[0], w.shape[0]
    padded = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[i] * padded[i:i + n] for i in range(width)))


def decays(s: Sizes, f, layer):
    """g (n, H, dk): a position's log decay a head and key channel, from
    the projection f (n, H x dk), in (`lower_bound`, 0)."""
    rate = jnp.exp(s.a_log_init + layer["a_log"])[None, :, None]
    return s.lower_bound * jax.nn.sigmoid(rate * (
        f.reshape(-1, s.heads, s.dk) + s.dt_bias_init + layer["dt_bias"]))


def recurrence(q, k, v, g, beta):
    """The delta rule position by position: q, k, g (n, H, dk), v (n, H,
    dv), beta (n, H). Returns o (n, H, dv)."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[:, :, None]             # a row a key channel
        mem = jnp.einsum("hkv,hk->hv", S, kt, precision=HIGHEST)
        u = (vt - mem) * bt[:, None]
        S = S + kt[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), F32),
                        (q, k, v, g, beta))
    return o


def _kda(s: Sizes, u, layer, quant):
    """A KDA mixer on one sequence: u (n, d_model) f32, normed."""
    n, H = u.shape[0], s.heads
    mixed = _conv_silu(_mm(u, layer["w_qkv"], quant), layer["conv"])
    q, k, v = jnp.split(mixed, [s.key_dim, 2 * s.key_dim], axis=-1)
    q = _l2(q.reshape(n, H, s.dk)) / math.sqrt(s.dk)
    k = _l2(k.reshape(n, H, s.dk))
    g = decays(s, _mm(u, layer["w_f"], quant), layer)
    beta = jax.nn.sigmoid(_mm(u, layer["w_b"], quant))
    o = recurrence(quant(q), quant(k), quant(v.reshape(n, H, s.dv)), g,
                   beta)
    z = _mm(u, layer["w_g"], quant).reshape(n, H, s.dv)
    y = _rms(o, layer["o_norm"], s.norm_eps) * jax.nn.sigmoid(z)
    return _mm(y.reshape(n, s.value_dim), layer["wo"], quant)


def _latent(s: Sizes, u, layer, positions, quant, remat=False):
    """Latent attention on one sequence in the expanded form, no cache: u
    (n, d_model) f32, normed."""
    n, H = u.shape[0], s.heads
    q = _mm(u, layer["wq"], quant).reshape(n, H, s.qk_head)
    kv_a = _mm(u, layer["wkv_a"], quant)
    c_kv = _rms(kv_a[:, :s.kv_lora], layer["kv_norm"], s.norm_eps)
    k_rope = _rope(kv_a[:, None, s.kv_lora:], positions, s.rope_theta)
    kv = _mm(c_kv, layer["wkv_b"], quant).reshape(n, H, s.nope + s.v_head)
    q = jnp.concatenate(
        [q[..., :s.nope], _rope(q[..., s.nope:], positions, s.rope_theta)],
        axis=-1)
    k = jnp.concatenate(
        [kv[..., :s.nope], jnp.broadcast_to(k_rope, (n, H, s.rope))],
        axis=-1)
    v = kv[..., s.nope:]
    causal = positions[:, None] >= positions[None, :]

    def one_head(qkv):
        """One head at a time, so that the (seq, seq) scores of all heads
        never exist together."""
        qh, kh, vh = qkv
        scores = jnp.einsum("qd,kd->qk", quant(qh), quant(kh),
                            precision=HIGHEST) / (s.qk_head ** 0.5)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("qk,kd->qd", quant(probs), quant(vh),
                          precision=HIGHEST)

    if remat:
        one_head = jax.checkpoint(one_head)
    out = jax.lax.map(one_head, tuple(a.transpose(1, 0, 2)
                                      for a in (q, k, v)))
    gate = jax.nn.sigmoid(_mm(u, layer["w_head_gate"], quant))     # (n, H)
    out = out.transpose(1, 0, 2) * gate[..., None]
    return _mm(out.reshape(n, H * s.v_head), layer["wo"], quant)


def route(s: Sizes, u, layer):
    """(slots (n, k), weights (n, k)) of tokens u (n, d_model), float32
    throughout and never rounded by the control. A sigmoid a slot; the bias
    moves the choice only. The group limit written out: the slots are
    `n_group` runs of `experts / n_group`; a group's score is the sum of
    its two largest `score + bias`; a group is kept if fewer than
    `topk_group` groups score higher; the slots of the others cannot be
    chosen. The weights are the chosen scores over their sum times
    `route_scale`."""
    scores = jax.nn.sigmoid(jnp.matmul(u, layer["router"].astype(F32),
                                       precision=HIGHEST))
    choice = scores + layer["router_bias"].astype(F32)
    n, per = u.shape[0], s.experts // s.n_group
    by_group = jnp.sort(choice.reshape(n, s.n_group, per), axis=-1)
    group = by_group[..., -1] + by_group[..., -2]           # (n, n_group)
    higher = jnp.sum(group[:, None, :] > group[:, :, None], axis=-1)
    kept = jnp.repeat(higher < s.topk_group, per, axis=-1)  # (n, experts)
    _, top_e = jax.lax.top_k(jnp.where(kept, choice, -jnp.inf), s.top_k)
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


def _swiglu(h, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(h, gate, quant)) * _mm(h, up, quant), down,
               quant)


def held_part(s: Sizes, u, layer, quant):
    """This share of the routed experts: the held experts walked one by
    one, each lifted to float32 alone, a token's weight zero for an expert
    it did not choose. Nothing for the experts held elsewhere."""
    mine = slot_weights(s, u, layer)[:, s.first_held:s.first_held + s.held]

    def one(acc, ew):
        gate, up, down, w = ew
        return acc + w[:, None] * _swiglu(
            u, gate.astype(F32), up.astype(F32), down.astype(F32),
            quant), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (layer["moe_gate"], layer["moe_up"],
                           layer["moe_down"], mine.T))
    return acc


def shared_part(s: Sizes, u, layer, quant):
    """The shared expert, which every chip computes alike."""
    return _swiglu(u, layer["shared_gate"].astype(F32),
                   layer["shared_up"].astype(F32),
                   layer["shared_down"].astype(F32), quant)


_BIG = ("moe_gate", "moe_up", "moe_down")


def _block(s: Sizes, i: int, x, layer, positions, quant, remat=False):
    """Held layer i on one sequence: x (seq, d_model) f32. The experts'
    matrices are lifted one at a time inside; every other leaf here."""
    small = _lift({k: v for k, v in layer.items() if k not in _BIG})
    u = _rms(x, small["attn_norm"], s.norm_eps)
    if s.layer_types[i] == LATENT:
        x = x + _latent(s, u, small, positions, quant, remat)
    else:
        x = x + _kda(s, u, small, quant)
    u = _rms(x, small["mlp_norm"], s.norm_eps)
    if s.mlp_types[i] == DENSE:
        return x + _swiglu(u, small["gate"], small["up"], small["down"],
                           quant)
    return x + held_part(s, u, layer, quant) + shared_part(s, u, layer,
                                                           quant)


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
    positions = jnp.arange(tokens.shape[0])
    x = params["embed"].astype(F32)[tokens]
    for i, layer in enumerate(params["layers"]):
        block = functools.partial(_block, s, i, positions=positions,
                                  quant=quant, remat=remat)
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
    lambda s, i, x, layer, positions, control: _block(
        s, i, x, layer, positions, _QUANT[control]),
    static_argnums=(0, 1, 5))
_jit_head = jax.jit(
    lambda s, x, norm, head, start, rows, control: _head(
        s, x, norm, head, _QUANT[control], (start, rows)),
    static_argnums=(0, 5, 6))


def reference_rows(s: Sizes, params, tokens, start, rows: int,
                   control: bool = False):
    """Logits of positions start .. start + rows - 1 of one sequence that
    is padded at its end (every layer is causal, and a token's experts are
    its own, so the padding touches nothing before it). `control` rounds
    every matmul operand to fp8 instead, the recurrence's q, k and v among
    them; the routing, the decays' arithmetic and the state stay float32 in
    both. One jitted program a layer (this module's docstring says why)."""
    positions = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens].astype(F32)
    for i, layer in enumerate(params["layers"]):
        x = _jit_block(s, i, x, layer, positions, control)
    return _jit_head(s, x, params["final_norm"], params["lm_head"], start,
                     rows, control)


# ------------------------------------------------------------ required ops
def _mixer_params(s: Sizes, kind: str) -> int:
    if kind == LATENT:
        return (s.d_model * s.heads * s.qk_head
                + s.d_model * (s.kv_lora + s.rope)
                + s.kv_lora * s.heads * (s.nope + s.v_head)
                + s.heads * s.v_head * s.d_model + s.d_model * s.heads)
    return (s.d_model * (s.channels + s.key_dim + s.value_dim + s.heads)
            + s.value_dim * s.d_model)


def _ffn_params(s: Sizes, mlp: str) -> float:
    if mlp == DENSE:
        return 3 * s.d_model * s.d_ff
    return (s.d_model * s.experts + 3 * s.d_model * s.shared_ff
            + s.top_k * s.held / s.experts * 3 * s.d_model * s.moe_ff)


def matmul_params(s: Sizes) -> float:
    """Parameters that multiply a token's activations on this chip: every
    layer's projections, the router at its whole width, the shared expert,
    and of the experts the `top_k * held / experts` a token's choices give
    this share when the routing is even (2 experts a layer at the published
    sizes); the output head. Not the embedding table, the norms, the
    convolution's taps or the gates' constants."""
    return (sum(_mixer_params(s, k) + _ffn_params(s, m)
                for k, m in zip(s.layer_types, s.mlp_types))
            + s.d_model * s.vocab)


def _recurrence_flops(s: Sizes) -> float:
    """One position of one KDA layer, position by position: the decay,
    S'^T k, the rank-one update and S^T q, each over a head's dk x dv."""
    return 7.0 * s.heads * s.dk * s.dv


def attention_flops_per_token(s: Sizes, seq_len: int,
                              passes: int = 3) -> float:
    """The mixers' own work per token, all layers: a latent layer's causal
    QK^T and PV in the expanded form (2 x (nope + rope) and 2 x v
    operations a head and key seen), a KDA layer's recurrence; the backward
    is twice the forward (`passes` 3)."""
    latent = 2.0 * s.heads * (s.qk_head + s.v_head) * (seq_len + 1) / 2.0
    return passes * (s.attentions * latent
                     + len(s.of_kind(LINEAR)) * _recurrence_flops(s))


def train_flops_per_token(s: Sizes, seq_len: int) -> float:
    """Forward + backward: 6 per matmul parameter plus the mixers."""
    return 6.0 * matmul_params(s) + attention_flops_per_token(s, seq_len)


def kda_step_call(s: Sizes, state_slots: int, itemsize: int = 2) -> dict:
    """The KDA layers' decode recurrence for `state_slots` lane-steps
    (`engine.decode_dispatch`'s `state_slots`, summed over steps),
    whatever implements it: a layer's float32 state read and written, q, k
    and v in, the float32 decays (a number a head and key channel) and
    betas in, the float32 outputs out. Bytes bound it. The convolution's
    tail is gathered and scattered outside the kernel's events and is not
    counted here (`cache.state_bytes_share.docs16k` counts it)."""
    n = len(s.of_kind(LINEAR))
    state = s.dk * s.value_dim * 4
    io = (s.channels * itemsize + (s.key_dim + s.heads) * 4
          + s.value_dim * 4)
    return {"flops": n * state_slots * _recurrence_flops(s),
            "bytes": float(n * state_slots * (2 * state + io))}


def kda_chunk_call(s: Sizes, tokens: int, itemsize: int = 2) -> dict:
    """One prefill's chunked recurrence over all KDA layers at `tokens`
    true positions, chunks of C = `chunk`, as the chunked algorithm needs it
    a head and chunk, whatever implements it: the lower triangles of the
    decayed K K^T and Q K^T (C^2 dk each), the unit lower-triangular solve
    (C^3 / 3), its products with V and K and the triangle times U (C^2 (2
    dv + dk)), and the three products with the state (2 C dk dv each); q,
    k, v read, the float32 log decays (a number a head and key channel)
    and betas read, the outputs and the last state written. The decays'
    exponentials and the scaling of keys and queries by them (some 6 C dk
    a head and chunk, not on the MXU) are not counted, nor is what a
    padded bucket holds past the prompt: the program's cost."""
    n, C = len(s.of_kind(LINEAR)), s.chunk
    per_chunk = (2.0 * C * C * s.dk + C ** 3 / 3.0
                 + C * C * (2.0 * s.dv + s.dk) + 6.0 * C * s.dk * s.dv)
    chunks = tokens / float(C)
    nbytes = (tokens * (s.channels + s.value_dim) * itemsize
              + tokens * (s.key_dim + s.heads) * 4 + s.dk * s.value_dim * 4)
    return {"flops": n * s.heads * chunks * per_chunk,
            "bytes": float(n * nbytes)}


def mla_decode_call(s: Sizes, live_positions: int, lanes: int,
                    itemsize: int = 2) -> dict:
    """Decode attention over the latent cache, the latent layers of a step
    (one of seven), as the absorbed algorithm needs it, for one step or
    (the counts being sums) for many: every live position's row (`kv_lora +
    rope` numbers) read once a latent layer and used as key and as value; a
    lane's queries in (`heads` rows of that width) and latent outputs out
    (`heads * kv_lora`); scores are 2 * (kv_lora + rope) and the output 2 *
    kv_lora operations a head and position. A row's padding to whole lanes
    and a page's unused tail, which the kernel reads too, do not count."""
    row = s.cache_row
    rows = live_positions * row * itemsize
    q_and_o = lanes * s.heads * (row + s.kv_lora) * itemsize
    return {"flops": 2.0 * s.heads * (row + s.kv_lora) * live_positions
            * s.attentions,
            "bytes": float(s.attentions * (rows + q_and_o))}


def moe_gmm_call(s: Sizes, pairs: int, experts_touched: int,
                 itemsize: int = 2) -> dict:
    """The held experts' three grouped matmuls, as the algorithm needs
    them, `pairs` (token, held expert) pairs and `experts_touched` held
    experts with at least one pair, both summed over layers and steps: the
    three matrices of each touched expert read once, each pair's activation
    in and result out; 6 * d_model * moe_ff operations a pair. An expert
    that got no pair and an expert held elsewhere cost nothing."""
    weights = experts_touched * 3 * s.d_model * s.moe_ff * itemsize
    acts = pairs * 2 * s.d_model * itemsize
    return {"flops": 6.0 * s.d_model * s.moe_ff * pairs,
            "bytes": float(weights + acts)}


def flash_prefill_call(s: Sizes, tokens: int, itemsize: int = 2) -> dict:
    """The flash forward of one prefill of `tokens` true tokens, the latent
    layers, in the expanded form at the true widths: QK^T is 2 *
    tokens^2/2 * (nope + rope) operations a head and PV 2 * tokens^2/2 * v;
    queries and keys in at `nope + rope` numbers a head and token, values in
    and outputs out at `v`, the row statistic out in float32. The padding of
    a prompt to its bucket, which the kernel computes and masks, does not
    count."""
    pairs = tokens * tokens / 2.0
    flops = 2.0 * pairs * s.heads * (s.qk_head + s.v_head)
    nbytes = tokens * s.heads * (
        (2 * s.qk_head + 2 * s.v_head) * itemsize + 4)
    return {"flops": s.attentions * flops,
            "bytes": float(s.attentions * nbytes)}
