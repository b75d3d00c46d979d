"""Everything the yardstick knows of one architecture: the decoder of double
layers with a shortcut-connected mixture of experts (LongCat-Flash-Chat):
two latent attentions (MLA) and two dense SwiGLU feed-forwards in line, and
a routed feed-forward beside them that reads the first half's normed stream
and is added at the layer's end. `benchmarks/models/dense_gqa.py` states the
interface this file implements (`harness/modelcfg.INTERFACE`).

A layer, as the reference computes it (float32, precision `highest`, nothing
of the program imported), `x` its input, every `N` an RMSNorm of its own:

    h  = x + MLA_0(N(x))
    u  = N(h)
    m  = MoE(u)
    h  = h + FFN_0(u)
    h  = h + MLA_1(N(h))
    h  = h + FFN_1(N(h))
    x' = h + m

- `MLA`, in the expanded form only (no absorption, no cache): `c_q = a_q
  RMSNorm(x W_qa)` with `a_q = sqrt(hidden_size / q_lora_rank)`
  (`mla_scale_q_lora`); `q = c_q W_qb`, heads of `[q_nope | q_rope]`; `[c_kv
  | k_rope] = x W_kva`, `c_kv = a_kv RMSNorm(c_kv)` with `a_kv =
  sqrt(hidden_size / kv_lora_rank)` (`mla_scale_kv_lora`; `k_rope` does not
  carry it), `k_rope = RoPE(k_rope)` one for all heads; `[k_nope | v]` a head
  `= c_kv W_kvb`; `q_rope = RoPE(q_rope)`; scores `q . [k_nope | k_rope] /
  sqrt(nope + rope)`, causal softmax, `o = concat_h(P v) W_o`. Keys are 192
  wide and values 128.
- `MoE(u)`: `s = softmax(u W_r)` in float32 over `n_routed_experts +
  zero_expert_num` slots (512 + 256 as published), no bias in the logits;
  the top `moe_topk` of `s + b` chosen (`b`, `e_score_correction_bias`,
  moves the choice only); weights `w = routed_scaling_factor x s` at the
  chosen slots, not renormalised; the first 512 slots are SwiGLU experts,
  the last 256 are `zero_expert_type: identity`:
  `MoE(u) = sum over chosen i < 512 of w_i E_i(u) + (sum over chosen i >=
  512 of w_i) u`.
- **One chip's share**: the configuration holds `n_routed_experts` experts
  of the published count, `deployment.experts_held = [first, last)`. The
  router keeps its published width; the reference, like the program, adds
  the held experts' parts and the identity part and nothing for the experts
  held elsewhere. The vocabulary is the configuration's slice: a smaller
  vocabulary, for the embedding, the head and the traffic alike.

Departures from the published description, none with effect on the
mathematics: norm scales are stored as w with the layer multiplying by
(1 + w), the program's convention, so one set of seeded weights feeds both;
the rotary pairs inside the 64 rope dimensions are split halves (i with
i + 32), `harness/reference.py`'s convention; the latent norms use the
file's `rms_norm_eps`; each held expert is computed for every token and
weighted by zero where the token did not choose it, one expert lifted to
float32 at a time. `e_score_correction_bias` is a seeded leaf of std
`BIAS_STD` (a trained model's is learned; zero would leave choice and weight
indistinguishable).

`reference_rows` runs a layer's five parts (two attentions, two
feed-forwards, the experts) as one jitted program each, so that only one
part's matrices are float32 at a time: it has to fit beside 10.35 GB of
served weights and the pool.

The weight tree has the program's layout (`ray_tpu/models/
shortcut_mla_moe.py`): a layer holds `attn` (two dicts), `ffn` (two dicts)
and the router and held experts.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import (F32, HIGHEST, _ident, _mm, _rms,
                                          _rope, fp8_round)

# std of the seeded e_score_correction_bias: of 768 softmax scores at the
# published widths the twelfth largest is 0.0116 and the thirteenth 0.0006
# under it, and this std moves about one choice in 28. A larger one makes
# whole slots popular (at 0.003 a slot two std up is chosen several times as
# often), and which of the 16 held slots drew what then sets how many
# experts a step reads: the seed's luck, where a trained bias evens the
# load out (PERF.md section 6, PR 44, item 8)
BIAS_STD = 0.0005


# ------------------------------------------------------------ sizes
@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    layers: int             # double layers
    heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v_head: int
    d_ff: int
    moe_ff: int
    experts: int            # of the whole layer, as published
    zero: int               # slots that compute nothing
    first_held: int
    held: int               # experts this chip holds
    top_k: int
    route_scale: float
    scale_q: bool
    scale_kv: bool
    rope_theta: float
    norm_eps: float

    @property
    def qk_head(self) -> int:
        return self.nope + self.rope

    @property
    def slots(self) -> int:
        """The router's width."""
        return self.experts + self.zero

    @property
    def attentions(self) -> int:
        """Rows of the latent pool: two a layer."""
        return 2 * self.layers

    @property
    def cache_row(self) -> int:
        """Numbers a position costs an attention in the latent cache."""
        return self.kv_lora + self.rope

    @property
    def a_q(self) -> float:
        return math.sqrt(self.d_model / self.q_lora) if self.scale_q else 1.0

    @property
    def a_kv(self) -> float:
        return (math.sqrt(self.d_model / self.kv_lora) if self.scale_kv
                else 1.0)


def sizes(cfg: dict) -> Sizes:
    if cfg.get("zero_expert_type", "identity") != "identity":
        raise ValueError("only identity zero-computation experts are "
                         "written down here")
    held = cfg["n_routed_experts"]
    first, last = cfg["deployment"]["experts_held"]
    if last - first != held:
        raise ValueError(f"deployment.experts_held {[first, last]} is not "
                         f"the {held} experts of n_routed_experts")
    return Sizes(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layers=cfg["num_layers"], heads=cfg["num_attention_heads"],
        q_lora=cfg["q_lora_rank"], kv_lora=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v_head=cfg["v_head_dim"], d_ff=cfg["ffn_hidden_size"],
        moe_ff=cfg["expert_ffn_hidden_size"],
        experts=cfg.get("published", {}).get("n_routed_experts", held),
        zero=cfg["zero_expert_num"], first_held=first, held=held,
        top_k=cfg["moe_topk"],
        route_scale=float(cfg["routed_scaling_factor"]),
        scale_q=bool(cfg["mla_scale_q_lora"]),
        scale_kv=bool(cfg["mla_scale_kv_lora"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]))


def tiny(cfg: dict) -> dict:
    """The same file at rehearsal size: control flow on the CPU, never a
    measurement. Every mechanism stays (two double layers, values narrower
    than keys, both scales other than 1, a share of 4 of 16 experts that
    does not start at 0, 8 slots that compute nothing)."""
    small = dict(cfg)
    small.update(hidden_size=64, num_layers=2, num_attention_heads=4,
                 q_lora_rank=32, kv_lora_rank=96, qk_nope_head_dim=16,
                 qk_rope_head_dim=16, v_head_dim=8, ffn_hidden_size=128,
                 expert_ffn_hidden_size=32, n_routed_experts=4,
                 zero_expert_num=8, moe_topk=4, vocab_size=512,
                 published={**cfg.get("published", {}),
                            "n_routed_experts": 16},
                 deployment={**cfg["deployment"], "experts_held": [4, 8]})
    return small


# ------------------------------------------------------------ weights
def weight_shapes(s: Sizes) -> dict:
    """Normal weights of 0.02, output projections scaled down by depth (four
    residual additions a double layer), norm scales 0.1 around the identity,
    the router's bias `BIAS_STD`; the layout the program's `ShortcutMLAMoE`
    holds, the held experts alone."""
    e, H = s.d_model, s.heads
    std = 0.02
    out_std = std / math.sqrt(4 * s.layers)

    def attn():
        return {
            "attn_norm": ((e,), 0.1),
            "wq_a": ((e, s.q_lora), std),
            "q_norm": ((s.q_lora,), 0.1),
            "wq_b": ((s.q_lora, H * s.qk_head), std),
            "wkv_a": ((e, s.kv_lora + s.rope), std),
            "kv_norm": ((s.kv_lora,), 0.1),
            "wkv_b": ((s.kv_lora, H * (s.nope + s.v_head)), std),
            "wo": ((H * s.v_head, e), out_std)}

    def ffn():
        return {"mlp_norm": ((e,), 0.1), "gate": ((e, s.d_ff), std),
                "up": ((e, s.d_ff), std), "down": ((s.d_ff, e), out_std)}

    def layer():
        E, f = s.held, s.moe_ff
        return {"attn": [attn(), attn()], "ffn": [ffn(), ffn()],
                "router": ((e, s.slots), std),
                "router_bias": ((s.slots,), BIAS_STD),
                "moe_gate": ((E, e, f), std), "moe_up": ((E, e, f), std),
                "moe_down": ((E, f, e), out_std)}

    return {"embed": ((s.vocab, e), std), "final_norm": ((e,), 0.1),
            "lm_head": ((e, s.vocab), std),
            "layers": [layer() for _ in range(s.layers)]}


def param_count(s: Sizes) -> int:
    from benchmarks.harness import weights
    return weights.param_count(weight_shapes(s))


# ------------------------------------------------------------ the program
def program_config(cfg: dict, max_seq_len: int, **extra):
    """The program's ShortcutMLAMoEConfig for this file."""
    from ray_tpu.models.shortcut_mla_moe import ShortcutMLAMoEConfig
    s = sizes(cfg)
    dtype = cfg.get("torch_dtype", "bfloat16")
    return ShortcutMLAMoEConfig(
        vocab_size=s.vocab, d_model=s.d_model, n_layers=s.layers,
        n_heads=s.heads, q_lora_rank=s.q_lora, kv_lora_rank=s.kv_lora,
        qk_nope_head_dim=s.nope, qk_rope_head_dim=s.rope,
        v_head_dim=s.v_head, d_ff=s.d_ff, moe_intermediate_size=s.moe_ff,
        n_routed_experts=s.experts, zero_expert_num=s.zero,
        experts_held=(s.first_held, s.held), num_experts_per_tok=s.top_k,
        routed_scaling_factor=s.route_scale, norm_topk_prob=False,
        scoring_func="softmax", mla_scale_q_lora=s.scale_q,
        mla_scale_kv_lora=s.scale_kv, max_seq_len=max_seq_len,
        rope_theta=s.rope_theta, norm_eps=s.norm_eps,
        **{"dtype": dtype, "param_dtype": dtype, **extra})


def train_model(cfg: dict, seq_len: int):
    from ray_tpu.models.shortcut_mla_moe import ShortcutMLAMoE
    return ShortcutMLAMoE(program_config(cfg, max_seq_len=seq_len))


# ------------------------------------------------------------ the reference
def _lift(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _attention(s: Sizes, h, attn, positions, quant, remat=False):
    """MLA on one sequence in the expanded form: h (n, d_model) f32, `attn`
    one attention's leaves in float32."""
    n = h.shape[0]
    c_q = s.a_q * _rms(_mm(h, attn["wq_a"], quant), attn["q_norm"],
                       s.norm_eps)
    q = _mm(c_q, attn["wq_b"], quant).reshape(n, s.heads, s.qk_head)
    kv_a = _mm(h, attn["wkv_a"], quant)
    c_kv = s.a_kv * _rms(kv_a[:, :s.kv_lora], attn["kv_norm"], s.norm_eps)
    k_rope = _rope(kv_a[:, None, s.kv_lora:], positions, s.rope_theta)
    kv = _mm(c_kv, attn["wkv_b"], quant).reshape(
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
    return _mm(out, attn["wo"], quant)


def route(s: Sizes, u, layer):
    """(slots (n, k), weights (n, k)) of tokens u (n, d_model), float32
    throughout and never rounded by the control: a softmax over every slot,
    the bias moves the choice, the weight is `route_scale` times the score
    and is not renormalised."""
    scores = jax.nn.softmax(jnp.matmul(u, layer["router"].astype(F32),
                                       precision=HIGHEST), axis=-1)
    _, top_e = jax.lax.top_k(scores + layer["router_bias"].astype(F32),
                             s.top_k)
    return top_e, jnp.take_along_axis(scores, top_e, axis=-1) * s.route_scale


def _swiglu(h, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(h, gate, quant)) * _mm(h, up, quant), down,
               quant)


def slot_weights(s: Sizes, u, layer):
    """(n, slots) float32: a token's weight at each slot it chose, zero
    elsewhere."""
    top_e, top_w = route(s, u, layer)
    n = u.shape[0]
    return jnp.zeros((n, s.slots), F32).at[
        jnp.arange(n)[:, None], top_e].add(top_w)


def _experts(s: Sizes, u, layer, quant):
    """This share of `MoE(u)`: the held experts walked one by one, each
    lifted to float32 alone, a token's weight zero for an expert it did not
    choose; the identity part of every token; nothing for the experts held
    elsewhere."""
    weight = slot_weights(s, u, layer)
    mine = weight[:, s.first_held:s.first_held + s.held]

    def one(acc, ew):
        gate, up, down, w = ew
        y = _swiglu(u, gate.astype(F32), up.astype(F32), down.astype(F32),
                    quant)
        return acc + w[:, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (layer["moe_gate"], layer["moe_up"],
                         layer["moe_down"], mine.T))
    return y + jnp.sum(weight[:, s.experts:], axis=-1, keepdims=True) * u


def _ffn(s: Sizes, u, ffn, quant):
    return _swiglu(u, ffn["gate"], ffn["up"], ffn["down"], quant)


# a layer's five parts, each with its own matrices alone lifted to float32
def _part_attn(s, h, attn, positions, quant, remat=False):
    attn = _lift(attn)
    return h + _attention(s, _rms(h, attn["attn_norm"], s.norm_eps), attn,
                          positions, quant, remat)


def _part_experts(s, h, moe, norm, quant):
    """m = MoE(N(h)) with the first feed-forward's norm; `moe` a layer's
    router and held experts."""
    return _experts(s, _rms(h, norm.astype(F32), s.norm_eps), moe, quant)


def _part_ffn(s, h, ffn, quant):
    ffn = _lift(ffn)
    return h + _ffn(s, _rms(h, ffn["mlp_norm"], s.norm_eps), ffn, quant)


_MOE = ("router", "router_bias", "moe_gate", "moe_up", "moe_down")


def _block(s: Sizes, x, layer, positions, quant, remat=False,
           parts=(_part_attn, _part_experts, _part_ffn)):
    """One double layer on one sequence: x (seq, d_model) f32. `parts` are
    the three functions above or their jitted twins (`_JIT_PARTS`), which
    take the control's flag where these take `quant`."""
    attn, experts, ffn = parts
    h = attn(s, x, layer["attn"][0], positions, quant, remat)
    m = experts(s, h, {k: layer[k] for k in _MOE}, layer["ffn"][0][
        "mlp_norm"], quant)
    h = ffn(s, h, layer["ffn"][0], quant)
    h = attn(s, h, layer["attn"][1], positions, quant, remat)
    h = ffn(s, h, layer["ffn"][1], quant)
    return h + m


def _head(s: Sizes, x, params, quant, window=None):
    if window is not None:
        x = jax.lax.dynamic_slice_in_dim(x, window[0], window[1], axis=0)
    x = _rms(x, params["final_norm"].astype(F32), s.norm_eps)
    return _mm(x, params["lm_head"].astype(F32), quant)


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
    return _head(s, x, params, quant, window)


def loss_fn(s: Sizes, params, tokens, quant=_ident, remat=False):
    """Mean next-token cross-entropy of one sequence, tokens (seq,)."""
    logits = logits_fn(s, params, tokens, quant, remat=remat)[:-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return -jnp.mean(picked)


_QUANT = {False: _ident, True: fp8_round}
_JIT_PARTS = (
    jax.jit(lambda s, h, attn, positions, control, remat=False: _part_attn(
        s, h, attn, positions, _QUANT[control]), static_argnums=(0, 4, 5)),
    jax.jit(lambda s, h, moe, norm, control: _part_experts(
        s, h, moe, norm, _QUANT[control]), static_argnums=(0, 4)),
    jax.jit(lambda s, h, ffn, control: _part_ffn(
        s, h, ffn, _QUANT[control]), static_argnums=(0, 3)))
_jit_head = jax.jit(
    lambda s, x, norm, head, start, rows, control: _head(
        s, x, {"final_norm": norm, "lm_head": head}, _QUANT[control],
        (start, rows)), static_argnums=(0, 5, 6))


def reference_rows(s: Sizes, params, tokens, start, rows: int,
                   control: bool = False):
    """Logits of positions start .. start + rows - 1 of one sequence that
    is padded at its end (causal, and a token's experts are its own, so
    the padding touches nothing before it). `control` rounds every matmul
    operand to fp8 instead; the routing stays float32 in both. One jitted
    program a part of a layer (this module's docstring says why)."""
    positions = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens].astype(F32)
    for layer in params["layers"]:
        x = _block(s, x, layer, positions, control, parts=_JIT_PARTS)
    return _jit_head(s, x, params["final_norm"], params["lm_head"], start,
                     rows, control)


# ------------------------------------------------------------ required ops
def _attn_params(s: Sizes) -> int:
    return (s.d_model * s.q_lora + s.q_lora * s.heads * s.qk_head
            + s.d_model * (s.kv_lora + s.rope)
            + s.kv_lora * s.heads * (s.nope + s.v_head)
            + s.heads * s.v_head * s.d_model)


def matmul_params(s: Sizes) -> float:
    """Parameters that multiply a token's activations on this chip: a
    layer's two attentions and two dense feed-forwards, the router at its
    whole width, and of the experts the `top_k * held / slots` a token's
    choices give this share when the routing is even (0.25 experts a layer
    at the published sizes; the identity slots multiply nothing); the
    output head. Not the embedding table, not the norms."""
    layer = (2 * _attn_params(s) + 2 * 3 * s.d_model * s.d_ff
             + s.d_model * s.slots
             + s.top_k * s.held / s.slots * 3 * s.d_model * s.moe_ff)
    return s.layers * layer + s.d_model * s.vocab


def attention_flops_per_token(s: Sizes, seq_len: int,
                              passes: int = 3) -> float:
    """Causal attention per token, all attentions, in the expanded form:
    QK^T is 2 * seq/2 * heads * (nope + rope) operations and PV 2 * seq/2 *
    heads * v forward; the backward is twice that (`passes` 3)."""
    return passes * float(seq_len) * s.heads * (s.qk_head + s.v_head) \
        * s.attentions


def train_flops_per_token(s: Sizes, seq_len: int) -> float:
    """Forward + backward: 6 per matmul parameter plus causal attention."""
    return 6.0 * matmul_params(s) + attention_flops_per_token(s, seq_len)


def mla_decode_call(s: Sizes, live_positions: int, lanes: int,
                    itemsize: int = 2) -> dict:
    """Decode attention over the latent cache, all of a step's attentions
    (two a layer: 8 pool rows at 4 layers), as the absorbed algorithm needs
    it, for one step or (the counts being sums) for many: every live
    position's row (`kv_lora + rope` numbers) read once an attention and
    used as key and as value; a lane's queries in (`heads` rows of that
    width) and latent outputs out (`heads * kv_lora`); scores are 2 *
    (kv_lora + rope) and the output 2 * kv_lora operations a head and
    position. A row's padding to whole lanes and a page's unused tail, which
    the kernel reads too, do not count."""
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
    that got no pair, a slot that computes nothing and an expert held
    elsewhere cost nothing."""
    weights = experts_touched * 3 * s.d_model * s.moe_ff * itemsize
    acts = pairs * 2 * s.d_model * itemsize
    return {"flops": 6.0 * s.d_model * s.moe_ff * pairs,
            "bytes": float(weights + acts)}


def flash_prefill_call(s: Sizes, tokens: int, itemsize: int = 2) -> dict:
    """The flash forward of one prefill of `tokens` true tokens, all
    attentions, in the expanded form at the true widths: QK^T is 2 *
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
