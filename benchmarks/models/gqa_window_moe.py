"""Everything the yardstick knows of one architecture: the GQA decoder whose
layers are named one by one (`laguna`: Laguna-XS.2) — full attention or a
sliding window, each kind with its own number of query heads and its own
rotary scheme, a per-head output gate, a leading dense SwiGLU layer and
then layers of routed plus one shared expert. `benchmarks/models/
dense_gqa.py` states the interface this file implements
(`harness/modelcfg.INTERFACE`).

The layers, as the reference computes them (float32, precision `highest`,
nothing of the program imported), `h = RMSNorm(x)` the normed input of
layer i, of kind `layer_types[i]` with `heads[i]` query heads over
`kv_heads` kv heads of `head_dim`:

- attention: `q = h W_q`, `k = h W_k`, `v = h W_v`; q and k rotated by the
  kind's scheme (below); scores `q_i . k_j / sqrt(head_dim)` for `j <= i`,
  on a sliding layer only for `i - j < window` (the mask is written out,
  (seq, seq) a head, one head at a time); softmax; `o_h = P_h v`; the gate
  `g = sigmoid(h W_g)`, one number a head; `attn = concat_h(g_h o_h) W_o`.
- rotary: the leading `head_dim x partial_rotary_factor` numbers of a head
  turn, split halves inside that part, the rest pass. `default`: pair i at
  `theta^(-2i/rot)`. `yarn`: pair i at a blend of that and 1/`factor` of
  it, `ramp_i = clip((i - low) / (high - low), 0, 1)` with `low` / `high`
  the floor / ceiling of `rot ln(original_max / (beta 2 pi)) / (2 ln
  theta)` at `beta_fast` / `beta_slow`, and cos and sin times
  `attention_factor`.
- feed-forward: SwiGLU where `mlp_layer_types[i]` is `dense`; where
  `sparse`, `s = sigmoid(h' W_r)` in float32, the top-k chosen, weights `s`
  at the chosen over their sum times `moe_routed_scaling_factor`, `y =
  sum_e w_e E_e(h') + S(h')`, every expert a SwiGLU, the weights on the
  outputs. No token is dropped.

Departures from the published description, none with effect on the
mathematics: norm scales are stored as w with the layer multiplying by
(1 + w), the program's convention, so one set of seeded weights feeds
both; each expert is computed for every token and weighted by zero where
the token did not choose it, one expert lifted to float32 at a time, which
is the sum over chosen experts and fits beside 10 GB of served weights and
cache at 7,808 positions. What `config.json` leaves to the modelling code
is listed in the configuration file under `assumed`.

`Sizes` holds the published sizes by kind of layer (`layer_types`, `heads`,
`mlp_types`, both `Rope`s, `window`). Of its fields the harness reads
`vocab`; `kernel.moe_gmm_roofline.batch32` reads this module's
`moe_gmm_call` (`d_model`, `moe_ff`), `kernel.full_decode_roofline.mixed8k`
and `kernel.window_decode_roofline.mixed8k` `full_decode_call` /
`window_decode_call` (the layers of each kind, `kv_dim`, `heads`),
`kernel.flash_window_roofline.mixed8k` `flash_window_call` (`window`, the
sliding layers' heads); `step.moe_ms.mixed8k`,
`moe.experts_touched_share.mixed8k` and `moe.load_max_over_mean.mixed8k`
read the configuration file's `mlp_layer_types` and `num_experts`.

The weight tree has the program's layout (`ray_tpu/models/
gqa_window_moe.py`): layers held one by one in a list.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import (F32, HIGHEST, _ident, _mm, _rms,
                                          fp8_round)

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


# ------------------------------------------------------------ sizes
@dataclasses.dataclass(frozen=True)
class Rope:
    theta: float
    kind: str                       # "default" or "yarn"
    partial: float                  # share of a head that turns
    factor: float = 1.0
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    kv_heads: int
    head_dim: int
    layer_types: Tuple[str, ...]
    heads: Tuple[int, ...]          # query heads, a layer
    mlp_types: Tuple[str, ...]
    window: int
    rope_full: Rope
    rope_sliding: Rope
    d_ff: int
    moe_ff: int
    shared_ff: int
    experts: int
    top_k: int
    route_scale: float
    norm_eps: float

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    def of_kind(self, kind: str) -> Tuple[int, ...]:
        """The layers of one kind of attention."""
        return tuple(i for i, k in enumerate(self.layer_types) if k == kind)

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.mlp_types) if k == SPARSE)


def _rope_of(p: dict) -> Rope:
    return Rope(theta=float(p["rope_theta"]), kind=p["rope_type"],
                partial=float(p.get("partial_rotary_factor", 1.0)),
                factor=float(p.get("factor", 1.0)),
                original_max=int(p.get("original_max_position_embeddings",
                                       0)),
                beta_fast=float(p.get("beta_fast", 32.0)),
                beta_slow=float(p.get("beta_slow", 1.0)),
                attention_factor=p.get("attention_factor"))


def sizes(cfg: dict) -> Sizes:
    n = cfg["num_hidden_layers"]
    lists = [cfg["layer_types"], cfg["num_attention_heads_per_layer"],
             cfg["mlp_layer_types"]]
    if any(len(x) != n for x in lists):
        raise ValueError(f"the per-layer lists name {[len(x) for x in lists]}"
                         f" layers, num_hidden_layers {n}")
    if cfg.get("moe_apply_router_weight_on_input") or not cfg.get("gating"):
        raise ValueError("router weights on the input, or attention without "
                         "its output gate, are not written here")
    ropes = cfg["rope_parameters"]
    return Sizes(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_types=tuple(cfg["layer_types"]),
        heads=tuple(cfg["num_attention_heads_per_layer"]),
        mlp_types=tuple(cfg["mlp_layer_types"]),
        window=cfg["sliding_window"], rope_full=_rope_of(ropes[FULL]),
        rope_sliding=_rope_of(ropes[SLIDING]),
        d_ff=cfg["intermediate_size"], moe_ff=cfg["moe_intermediate_size"],
        shared_ff=cfg["shared_expert_intermediate_size"],
        experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        route_scale=float(cfg["moe_routed_scaling_factor"]),
        norm_eps=float(cfg["rms_norm_eps"]))


def tiny(cfg: dict) -> dict:
    """The same file at rehearsal size: control flow on the CPU, never a
    measurement. Every mechanism stays: layers of both kinds with unlike
    head counts, a window the prompts pass many times over, YaRN on half a
    head, the gate, a dense layer and four of 8 experts top-2."""
    small = dict(cfg)
    ropes = {k: dict(v) if isinstance(v, dict) else v
             for k, v in cfg["rope_parameters"].items()}
    ropes[FULL].update(factor=8, original_max_position_embeddings=32,
                       beta_fast=8)
    small.update(hidden_size=64, num_key_value_heads=2, head_dim=16,
                 num_attention_heads=4,
                 num_attention_heads_per_layer=[
                     4 if k == FULL else 6 for k in cfg["layer_types"]],
                 sliding_window=32, rope_parameters=ropes,
                 intermediate_size=128, moe_intermediate_size=32,
                 shared_expert_intermediate_size=32, num_experts=8,
                 num_experts_per_tok=2, vocab_size=512)
    return small


# ------------------------------------------------------------ weights
def weight_shapes(s: Sizes) -> dict:
    """Normal weights of 0.02, output projections scaled down by depth, norm
    scales 0.1 around the identity; the per-layer layout the program's
    `GQAWindowMoE` holds."""
    e = s.d_model
    std = 0.02
    out_std = std / math.sqrt(2 * s.layers)

    def layer(i):
        q_dim = s.heads[i] * s.head_dim
        shapes = {
            "attn_norm": ((e,), 0.1),
            "wq": ((e, q_dim), std), "wk": ((e, s.kv_dim), std),
            "wv": ((e, s.kv_dim), std), "wo": ((q_dim, e), out_std),
            "wg": ((e, s.heads[i]), std),
            "mlp_norm": ((e,), 0.1),
        }
        if s.mlp_types[i] == DENSE:
            shapes.update(gate=((e, s.d_ff), std), up=((e, s.d_ff), std),
                          down=((s.d_ff, e), out_std))
            return shapes
        E, f, fs = s.experts, s.moe_ff, s.shared_ff
        shapes.update(
            router=((e, E), std),
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
    """The program's GQAWindowMoEConfig for this file."""
    from ray_tpu.models.gqa_window_moe import GQAWindowMoEConfig, RopeParams
    s = sizes(cfg)
    dtype = cfg.get("torch_dtype", "bfloat16")

    def rope(r: Rope):
        return RopeParams(
            rope_theta=r.theta, rope_type=r.kind,
            partial_rotary_factor=r.partial, factor=r.factor,
            original_max_position_embeddings=r.original_max,
            beta_fast=r.beta_fast, beta_slow=r.beta_slow,
            attention_factor=r.attention_factor)

    return GQAWindowMoEConfig(
        vocab_size=s.vocab, d_model=s.d_model, n_kv_heads=s.kv_heads,
        head_dim=s.head_dim, layer_types=s.layer_types,
        n_heads_per_layer=s.heads, mlp_layer_types=s.mlp_types,
        sliding_window=s.window, rope_full=rope(s.rope_full),
        rope_sliding=rope(s.rope_sliding), d_ff=s.d_ff,
        moe_intermediate_size=s.moe_ff,
        shared_expert_intermediate_size=s.shared_ff, num_experts=s.experts,
        num_experts_per_tok=s.top_k, routed_scaling_factor=s.route_scale,
        max_seq_len=max_seq_len,
        norm_eps=s.norm_eps,
        **{"dtype": dtype, "param_dtype": dtype, **extra})


def train_model(cfg: dict, seq_len: int):
    from ray_tpu.models.gqa_window_moe import GQAWindowMoE
    return GQAWindowMoE(program_config(cfg, max_seq_len=seq_len))


# ------------------------------------------------------------ the reference
def rope_frequencies(r: Rope, head_dim: int):
    """(inverse frequency of each rotated pair (rot / 2,), what cos and sin
    are multiplied by), from the formulas in the module's docstring."""
    rot = int(head_dim * r.partial)
    plain = 1.0 / (r.theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    if r.kind == "default":
        return plain, 1.0

    def correction(beta):
        return (rot * math.log(r.original_max / (beta * 2 * math.pi))
                / (2 * math.log(r.theta)))

    low = max(math.floor(correction(r.beta_fast)), 0)
    high = min(math.ceil(correction(r.beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    scale = r.attention_factor
    if scale is None:
        scale = 0.1 * math.log(r.factor) + 1.0
    return plain / r.factor * ramp + plain * (1.0 - ramp), float(scale)


def _rotate(x, positions, r: Rope):
    """x (n, heads, head_dim): its leading rotary part turned, split halves
    inside that part (pair i with i + rot / 2), the rest passed."""
    inv, scale = rope_frequencies(r, x.shape[-1])
    half = inv.shape[0]
    ang = positions.astype(F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _attention(s: Sizes, i: int, h, layer, positions, quant, remat):
    """Layer i's attention on one sequence: h (n, d_model) f32."""
    n = h.shape[0]
    H, hd = s.heads[i], s.head_dim
    sliding = s.layer_types[i] == SLIDING
    r = s.rope_sliding if sliding else s.rope_full
    q = _rotate(_mm(h, layer["wq"], quant).reshape(n, H, hd), positions, r)
    k = _rotate(_mm(h, layer["wk"], quant).reshape(n, s.kv_heads, hd),
                positions, r)
    v = _mm(h, layer["wv"], quant).reshape(n, s.kv_heads, hd)
    back = positions[:, None] - positions[None, :]      # i - j
    seen = back >= 0
    if sliding:
        seen = seen & (back < s.window)
    group = H // s.kv_heads

    def one_head(hq):
        """One head at a time, so that the (seq, seq) scores of all heads
        never exist together."""
        head, qh = hq
        kh = jnp.take(k, head // group, axis=1)
        vh = jnp.take(v, head // group, axis=1)
        scores = jnp.einsum("qd,kd->qk", quant(qh), quant(kh),
                            precision=HIGHEST) / (hd ** 0.5)
        scores = jnp.where(seen, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("qk,kd->qd", quant(probs), quant(vh),
                          precision=HIGHEST)

    if remat:
        one_head = jax.checkpoint(one_head)
    out = jax.lax.map(one_head, (jnp.arange(H), q.transpose(1, 0, 2)))
    out = out.transpose(1, 0, 2)                        # (n, H, hd)
    out = out * jax.nn.sigmoid(_mm(h, layer["wg"], quant))[..., None]
    return _mm(out.reshape(n, H * hd), layer["wo"], quant)


def route(s: Sizes, h, layer):
    """(experts (n, k), weights (n, k)) of tokens h (n, d_model), float32
    throughout and never rounded by the control."""
    scores = jax.nn.sigmoid(jnp.matmul(h, layer["router"].astype(F32),
                                       precision=HIGHEST))
    top_w, top_e = jax.lax.top_k(scores, s.top_k)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    return top_e, top_w * s.route_scale


def _swiglu(h, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(h, gate, quant)) * _mm(h, up, quant), down,
               quant)


def _experts(s: Sizes, h, layer, quant):
    """sum_e w_e E_e(h) + S(h): the experts walked one by one, each lifted
    to float32 alone, a token's weight zero for an expert it did not
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
    return y + _swiglu(h, layer["shared_gate"].astype(F32),
                       layer["shared_up"].astype(F32),
                       layer["shared_down"].astype(F32), quant)


_BIG = ("moe_gate", "moe_up", "moe_down")


def _block(s: Sizes, i: int, x, layer, positions, quant, remat=False):
    """Layer i on one sequence: x (seq, d_model) f32."""
    small = {k: (v if k in _BIG else v.astype(F32))
             for k, v in layer.items()}
    h = _rms(x, small["attn_norm"], s.norm_eps)
    x = x + _attention(s, i, h, small, positions, quant, remat)
    h = _rms(x, small["mlp_norm"], s.norm_eps)
    if s.mlp_types[i] == SPARSE:
        return x + _experts(s, h, small, quant)
    return x + _swiglu(h, small["gate"], small["up"], small["down"], quant)


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
def _attn_params(s: Sizes, i: int) -> int:
    q_dim = s.heads[i] * s.head_dim
    return (2 * s.d_model * q_dim + 2 * s.d_model * s.kv_dim
            + s.d_model * s.heads[i])


def matmul_params(s: Sizes) -> int:
    """Parameters that multiply a token's activations: every layer's
    attention projections and gate, a dense layer's feed-forward, and in an
    expert layer the router, the `top_k` experts a token chose and the
    shared expert; the output head. Not the embedding table, not the
    norms."""
    dense = 3 * s.d_model * s.d_ff
    moe = (s.d_model * s.experts + s.top_k * 3 * s.d_model * s.moe_ff
           + 3 * s.d_model * s.shared_ff)
    return (sum(_attn_params(s, i) + (moe if s.mlp_types[i] == SPARSE
                                      else dense)
                for i in range(s.layers)) + s.d_model * s.vocab)


def _keys_seen(seq_len: int, window: Optional[int]) -> float:
    """Keys the queries of a causal sequence see, summed over queries."""
    if window is None or seq_len <= window:
        return seq_len * (seq_len + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq_len - window) * float(window)


def attention_flops_per_token(s: Sizes, seq_len: int,
                              passes: int = 3) -> float:
    """Causal attention per token, all layers: QK^T and PV are each 2 x
    head_dim operations a head and key seen, a sliding layer's query seeing
    `window` keys at most; the backward is twice the forward (`passes`
    3)."""
    total = 0.0
    for i, kind in enumerate(s.layer_types):
        keys = _keys_seen(seq_len, s.window if kind == SLIDING else None)
        total += 4.0 * s.head_dim * s.heads[i] * keys / seq_len
    return passes * total


def train_flops_per_token(s: Sizes, seq_len: int) -> float:
    """Forward + backward: 6 per matmul parameter plus causal attention."""
    return 6.0 * matmul_params(s) + attention_flops_per_token(s, seq_len)


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


def _decode_call(s: Sizes, kind: str, positions: int, lanes: int,
                 itemsize: int) -> dict:
    """Decode attention of the layers of one kind over `positions` cache
    positions a layer (summed over lanes, and over steps where the counts
    are sums): each position's key and value read once a layer, each
    lane's queries in and outputs out; QK^T and PV 2 x head_dim operations
    each a query head and position."""
    flops = nbytes = 0.0
    for i in s.of_kind(kind):
        q_dim = s.heads[i] * s.head_dim
        flops += 4.0 * positions * q_dim
        nbytes += (2 * positions * s.kv_dim + 2 * lanes * q_dim) * itemsize
    return {"flops": flops, "bytes": nbytes}


def full_decode_call(s: Sizes, live_positions: int, lanes: int,
                     itemsize: int = 2) -> dict:
    """The full layers' decode attention: every position the lanes hold
    (`engine.decode_dispatch`'s `live_positions`). A page's unused tail,
    which the kernel copies too, does not count."""
    return _decode_call(s, FULL, live_positions, lanes, itemsize)


def window_decode_call(s: Sizes, window_positions_live: int, lanes: int,
                       itemsize: int = 2) -> dict:
    """The sliding layers' decode attention: the positions inside the
    lanes' windows (`window_positions_live`: `min(length, window)` a lane).
    What the ring's first and last page hold outside the window, which the
    kernel copies too, does not count."""
    return _decode_call(s, SLIDING, window_positions_live, lanes, itemsize)


def flash_window_call(s: Sizes, tokens: int, itemsize: int = 2) -> dict:
    """One prefill's windowed flash forward over all sliding layers, at
    `tokens` true positions: QK^T and PV for the keys inside each query's
    window, 2 x head_dim operations each a head and key; q, k, v read and
    the output written once. What a padded bucket or a block holds outside
    the window, which the kernel computes and masks, does not count."""
    keys = _keys_seen(tokens, s.window)
    flops = nbytes = 0.0
    for i in s.of_kind(SLIDING):
        flops += 4.0 * s.head_dim * s.heads[i] * keys
        nbytes += (2 * tokens * s.heads[i] * s.head_dim
                   + 2 * tokens * s.kv_dim) * itemsize
    return {"flops": flops, "bytes": nbytes}
