"""Everything the yardstick knows of one architecture: the decoder with
multi-head latent attention (MLA), a leading dense SwiGLU layer and then
layers of routed plus shared experts (`glm4_moe_lite`: GLM-4.7-Flash; the
DeepSeek-V2/V3 family of layers). `benchmarks/models/dense_gqa.py` states
the interface this file implements (`harness/modelcfg.INTERFACE`).

The layers, as the reference computes them (float32, precision `highest`,
nothing of the program imported), `x` the normed input of a layer:

- attention, every layer, in the expanded form only (no absorption, no
  cache): `c_q = RMSNorm(x W_qa)`; `q = c_q W_qb`, heads of `[q_nope |
  q_rope]`; `[c_kv | k_rope] = x W_kva`, `c_kv = RMSNorm(c_kv)`, `k_rope =
  RoPE(k_rope)` one for all heads; `[k_nope | v]` a head `= c_kv W_kvb`;
  `q_rope = RoPE(q_rope)`; scores `q . [k_nope | k_rope] / sqrt(nope +
  rope)`, causal softmax, `o = concat_h(P v) W_o`.
- feed-forward: SwiGLU in the first `first_k_dense_replace` layers; after
  them `s = sigmoid(x W_g)` in float32, the top-k of `s + b` chosen (`b`,
  `e_score_correction_bias`, moves the choice only), weights `s` at the
  chosen over their sum (`norm_topk_prob`) times `routed_scaling_factor`,
  `y = sum_i w_i E_i(x) + E_shared(x)`, every expert a SwiGLU. No token is
  dropped. `n_group` and `topk_group` are 1 in the published file, so there
  is no group limit to compute.

Departures from the published description, none with effect on the
mathematics: norm scales are stored as w with the layer multiplying by
(1 + w), the program's convention, so one set of seeded weights feeds both;
the rotary pairs inside the 64 rope dimensions are split halves (i with
i + 32), `harness/reference.py`'s convention; the latent norms use the
file's `rms_norm_eps`; each expert is computed for every token and weighted
by zero where the token did not choose it, one expert lifted to float32 at
a time, which is the sum over chosen experts and fits beside 9 GB of served
weights; the multi-token-prediction block is not held (it takes no part in
the next-token logits). `e_score_correction_bias` is a seeded leaf of std
`BIAS_STD` (a trained model's is learned; zero would leave choice and
weight indistinguishable).

The weight tree has the program's layout (`ray_tpu/models/mla_moe.py`):
layers held one by one in a list, `wkv_b` as `(latent, heads * (nope + v))`.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import (F32, HIGHEST, _ident, _mm, _rms,
                                          _rope, fp8_round)

# std of the seeded e_score_correction_bias: against sigmoid scores that
# spread by some 0.2 it moves about one choice in five
BIAS_STD = 0.05


# ------------------------------------------------------------ sizes
@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    layers: int
    heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v_head: int
    d_ff: int
    moe_ff: int
    experts: int
    shared: int
    top_k: int
    first_dense: int
    route_scale: float
    norm_topk: bool
    rope_theta: float
    norm_eps: float

    @property
    def qk_head(self) -> int:
        return self.nope + self.rope

    @property
    def moe_layers(self) -> int:
        return max(0, self.layers - self.first_dense)

    @property
    def cache_row(self) -> int:
        """Numbers a position costs a layer in the latent cache."""
        return self.kv_lora + self.rope


def sizes(cfg: dict) -> Sizes:
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not written down here")
    return Sizes(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
        q_lora=cfg["q_lora_rank"], kv_lora=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v_head=cfg["v_head_dim"], d_ff=cfg["intermediate_size"],
        moe_ff=cfg["moe_intermediate_size"],
        experts=cfg["n_routed_experts"], shared=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"],
        first_dense=cfg["first_k_dense_replace"],
        route_scale=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]))


def tiny(cfg: dict) -> dict:
    """The same file at rehearsal size: control flow on the CPU, never a
    measurement. Every mechanism stays (a dense layer, two expert layers,
    a shared expert, a latent narrower than the heads it feeds)."""
    small = dict(cfg)
    small.update(hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
                 num_key_value_heads=4, q_lora_rank=48, kv_lora_rank=96,
                 qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=32,
                 intermediate_size=128, moe_intermediate_size=32,
                 n_routed_experts=8, num_experts_per_tok=2, vocab_size=512)
    return small


# ------------------------------------------------------------ weights
def weight_shapes(s: Sizes) -> dict:
    """Normal weights of 0.02, output projections scaled down by depth, norm
    scales 0.1 around the identity, the router's bias `BIAS_STD`; the
    per-layer layout the program's `MLAMoE` holds."""
    e, H = s.d_model, s.heads
    std = 0.02
    out_std = std / math.sqrt(2 * s.layers)

    def layer(i):
        shapes = {
            "attn_norm": ((e,), 0.1),
            "wq_a": ((e, s.q_lora), std),
            "q_norm": ((s.q_lora,), 0.1),
            "wq_b": ((s.q_lora, H * s.qk_head), std),
            "wkv_a": ((e, s.kv_lora + s.rope), std),
            "kv_norm": ((s.kv_lora,), 0.1),
            "wkv_b": ((s.kv_lora, H * (s.nope + s.v_head)), std),
            "wo": ((H * s.v_head, e), out_std),
            "mlp_norm": ((e,), 0.1),
        }
        if i < s.first_dense:
            shapes.update(gate=((e, s.d_ff), std), up=((e, s.d_ff), std),
                          down=((s.d_ff, e), out_std))
            return shapes
        E, f, fs = s.experts, s.moe_ff, s.moe_ff * s.shared
        shapes.update(
            router=((e, E), std), router_bias=((E,), BIAS_STD),
            moe_gate=((E, e, f), std), moe_up=((E, e, f), std),
            moe_down=((E, f, e), out_std),
            shared_gate=((e, fs), std), shared_up=((e, fs), std),
            shared_down=((fs, e), out_std))
        return shapes

    return {"embed": ((s.vocab, e), std), "final_norm": ((e,), 0.1),
            "lm_head": ((e, s.vocab), std),
            "layers": [layer(i) for i in range(s.layers)]}


def param_count(s: Sizes) -> int:
    from benchmarks.harness import weights
    return weights.param_count(weight_shapes(s))


# ------------------------------------------------------------ the program
def program_config(cfg: dict, max_seq_len: int, **extra):
    """The program's MLAMoEConfig for this file."""
    from ray_tpu.models.mla_moe import MLAMoEConfig
    s = sizes(cfg)
    dtype = cfg.get("torch_dtype", "bfloat16")
    return MLAMoEConfig(
        vocab_size=s.vocab, d_model=s.d_model, n_layers=s.layers,
        n_heads=s.heads, q_lora_rank=s.q_lora, kv_lora_rank=s.kv_lora,
        qk_nope_head_dim=s.nope, qk_rope_head_dim=s.rope,
        v_head_dim=s.v_head, d_ff=s.d_ff, moe_intermediate_size=s.moe_ff,
        n_routed_experts=s.experts, n_shared_experts=s.shared,
        num_experts_per_tok=s.top_k, first_k_dense_replace=s.first_dense,
        routed_scaling_factor=s.route_scale, norm_topk_prob=s.norm_topk,
        scoring_func=cfg.get("scoring_func", "sigmoid"),
        max_seq_len=max_seq_len, rope_theta=s.rope_theta,
        norm_eps=s.norm_eps,
        **{"dtype": dtype, "param_dtype": dtype, **extra})


def train_model(cfg: dict, seq_len: int):
    from ray_tpu.models.mla_moe import MLAMoE
    return MLAMoE(program_config(cfg, max_seq_len=seq_len))


# ------------------------------------------------------------ the reference
def _attention(s: Sizes, h, layer, positions, quant, remat):
    """MLA on one sequence in the expanded form: h (n, d_model) f32."""
    n = h.shape[0]
    c_q = _rms(_mm(h, layer["wq_a"], quant), layer["q_norm"], s.norm_eps)
    q = _mm(c_q, layer["wq_b"], quant).reshape(n, s.heads, s.qk_head)
    kv_a = _mm(h, layer["wkv_a"], quant)
    c_kv = _rms(kv_a[:, :s.kv_lora], layer["kv_norm"], s.norm_eps)
    k_rope = _rope(kv_a[:, None, s.kv_lora:], positions, s.rope_theta)
    kv = _mm(c_kv, layer["wkv_b"], quant).reshape(
        n, s.heads, s.nope + s.v_head)
    q = jnp.concatenate(
        [q[..., :s.nope], _rope(q[..., s.nope:], positions, s.rope_theta)],
        axis=-1)
    k = jnp.concatenate(
        [kv[..., :s.nope],
         jnp.broadcast_to(k_rope, (n, s.heads, s.rope))], axis=-1)
    v = kv[..., s.nope:]
    causal = positions[:, None] >= positions[None, :]

    def one_head(qkv):
        """One head at a time, so that the (seq, seq) scores of all heads
        never exist together."""
        qh, kh, vh = qkv
        scores = jnp.einsum("qd,kd->qk", quant(qh), quant(kh),
                            precision=HIGHEST) / (s.qk_head ** 0.5)
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("qk,kd->qd", quant(probs), quant(vh),
                          precision=HIGHEST)

    if remat:
        one_head = jax.checkpoint(one_head)
    out = jax.lax.map(one_head, tuple(a.transpose(1, 0, 2)
                                      for a in (q, k, v)))
    out = out.transpose(1, 0, 2).reshape(n, s.heads * s.v_head)
    return _mm(out, layer["wo"], quant)


def route(s: Sizes, h, layer):
    """(experts (n, k), weights (n, k)) of tokens h (n, d_model), float32
    throughout and never rounded by the control: the bias moves the choice,
    the weight is the score alone."""
    scores = jax.nn.sigmoid(jnp.matmul(h, layer["router"].astype(F32),
                                       precision=HIGHEST))
    _, top_e = jax.lax.top_k(scores + layer["router_bias"].astype(F32),
                             s.top_k)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if s.norm_topk:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    return top_e, top_w * s.route_scale


def _swiglu(h, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(h, gate, quant)) * _mm(h, up, quant), down,
               quant)


def _experts(s: Sizes, h, layer, quant):
    """sum_i w_i E_i(h) + E_shared(h): the experts walked one by one, each
    lifted to float32 alone, a token's weight zero for an expert it did
    not choose."""
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
    return y + _swiglu(h, layer["shared_gate"].astype(F32),
                       layer["shared_up"].astype(F32),
                       layer["shared_down"].astype(F32), quant)


_BIG = ("moe_gate", "moe_up", "moe_down")


def _block(s: Sizes, x, layer, positions, quant, remat=False):
    """One layer on one sequence: x (seq, d_model) f32."""
    small = {k: (v if k in _BIG else v.astype(F32))
             for k, v in layer.items()}
    h = _rms(x, small["attn_norm"], s.norm_eps)
    x = x + _attention(s, h, small, positions, quant, remat)
    h = _rms(x, small["mlp_norm"], s.norm_eps)
    if "router" in layer:
        return x + _experts(s, h, small, quant)
    return x + _swiglu(h, small["gate"], small["up"], small["down"], quant)


def logits_fn(s: Sizes, params, tokens, quant=_ident, window=None,
              remat=False):
    """tokens (seq,) int32 -> logits (seq, vocab) f32 of one sequence, or
    of the `window` = (start, rows) of its positions."""
    positions = jnp.arange(tokens.shape[0])
    x = params["embed"].astype(F32)[tokens]
    for layer in params["layers"]:
        block = functools.partial(_block, s, positions=positions,
                                  quant=quant, remat=remat)
        if remat:       # the backward keeps one layer's activations
            block = jax.checkpoint(block)
        x = block(x, layer)
    if window is not None:
        x = jax.lax.dynamic_slice_in_dim(x, window[0], window[1], axis=0)
    x = _rms(x, params["final_norm"].astype(F32), s.norm_eps)
    return _mm(x, params["lm_head"].astype(F32), quant)


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
    is padded at its end (causal, and a token's experts are its own, so
    the padding touches nothing before it). `control` rounds every matmul
    operand to fp8 instead; the routing stays float32 in both."""
    quant = fp8_round if control else _ident
    return logits_fn(s, params, tokens, quant, window=(start, rows))


# ------------------------------------------------------------ required ops
def _attn_params(s: Sizes) -> int:
    return (s.d_model * s.q_lora + s.q_lora * s.heads * s.qk_head
            + s.d_model * (s.kv_lora + s.rope)
            + s.kv_lora * s.heads * (s.nope + s.v_head)
            + s.heads * s.v_head * s.d_model)


def matmul_params(s: Sizes) -> int:
    """Parameters that multiply a token's activations: every layer's
    attention projections, the dense layers' feed-forward, and in an expert
    layer the router, the `top_k` experts a token chose and the shared
    experts; the output head. Not the embedding table, not the norms."""
    dense = 3 * s.d_model * s.d_ff
    moe = (s.d_model * s.experts
           + (s.top_k + s.shared) * 3 * s.d_model * s.moe_ff)
    return (s.layers * _attn_params(s) + s.first_dense * dense
            + s.moe_layers * moe + s.d_model * s.vocab)


def attention_flops_per_token(s: Sizes, seq_len: int,
                              passes: int = 3) -> float:
    """Causal attention per token, all layers, in the expanded form: QK^T is
    2 * seq/2 * heads * (nope + rope) operations and PV 2 * seq/2 * heads *
    v forward; the backward is twice that (`passes` 3)."""
    return passes * float(seq_len) * s.heads * (s.qk_head + s.v_head) \
        * s.layers


def train_flops_per_token(s: Sizes, seq_len: int) -> float:
    """Forward + backward: 6 per matmul parameter plus causal attention."""
    return 6.0 * matmul_params(s) + attention_flops_per_token(s, seq_len)


def mla_decode_call(s: Sizes, live_positions: int, lanes: int,
                    itemsize: int = 2) -> dict:
    """Decode attention over the latent cache, all layers, as the absorbed
    algorithm needs it, for one step or (the counts being sums) for many:
    every live position's row (`kv_lora + rope` numbers) read once a layer
    and used as key and as value; a lane's queries in (`heads` rows of that
    width) and latent outputs out (`heads * kv_lora`); scores are 2 *
    (kv_lora + rope) and the output 2 * kv_lora operations a head and
    position. A row's padding to whole lanes and a page's unused tail, which
    the kernel reads too, do not count."""
    row = s.cache_row
    rows = live_positions * row * itemsize
    q_and_o = lanes * s.heads * (row + s.kv_lora) * itemsize
    return {"flops": 2.0 * s.heads * (row + s.kv_lora) * live_positions
            * s.layers,
            "bytes": float(s.layers * (rows + q_and_o))}


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
