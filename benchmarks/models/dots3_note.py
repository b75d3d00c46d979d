"""Everything the yardstick knows of one architecture: the decoder with
multi-head latent attention (MLA) of two geometries, named layer by layer
by `layer_types`: **full** layers whose softmax runs over the positions a
learned indexer chooses, **sliding** layers whose softmax runs over the
query's last `sliding_window_size` positions; a sigmoid gate a head and a
scale on both latents in either kind; a leading dense SwiGLU layer and then
layers of routed plus shared experts (`dots3_note`: dots3-note-prev's
language model). `benchmarks/models/dense_gqa.py` states the interface this
file implements (`harness/modelcfg.INTERFACE`).

The layers, as the reference computes them (float32, precision `highest`,
nothing of the program imported), `x` the normed input of a layer and `(H,
r_q, r_kv, nope, rope, v, theta)` the layer kind's own geometry (`Geo`):

- attention, both kinds, in the expanded form only (no absorption, no
  cache): `c_q = a_q RMSNorm(x W_qa)`, `q = c_q W_qb`, heads of `[q_nope |
  RoPE(q_rope)]`; `[c_kv | k_rope] = x W_kva`, `c_kv = a_kv RMSNorm(c_kv)`,
  `k_rope = RoPE(k_rope)` one for all heads; `[k_nope | v]` a head `= c_kv
  W_kvb`; scores `q . [k_nope | k_rope] / sqrt(nope + rope)`; `o = concat_h(
  sigmoid((x W_g)_h) (P v)_h) W_o`; `a_q = sqrt(hidden / r_q)`, `a_kv =
  sqrt(hidden / r_kv)`. One head at a time.
- a **sliding** layer: `P` the softmax over `s` with `0 <= t - s < window`,
  an explicit banded mask.
- a **full** layer: the indexer `q^I = RoPE(c_q W^I_q)`, `index_n_heads`
  heads of `index_head_dim`; `k^I = RoPE(LayerNorm(x W^I_k))`; `w = x W^I_w
  / sqrt(index_n_heads x index_head_dim)`; `I[t, s] = sum_j w[t, j]
  relu(q^I[t, j] . k^I[s])` for `s <= t`, an explicit matrix in blocks of
  queries; `S_t` = the `min(index_topk, t + 1)` positions of largest `I[t,
  s]` by `jax.lax.top_k` (a tie goes to the lower position), kept as a
  mask; `P` the softmax over `S_t`. The rotary is on the first
  `qk_rope_head_dim` of the `index_head_dim` numbers at the full layers'
  theta; `c_q` carries `a_q`.
- feed-forward: SwiGLU in the first `first_k_dense_replace` layers; after
  them `s = sigmoid(x W_g)` in float32 over all `published` experts, the
  top-k of `s + b` chosen, weights `s` at the chosen over their sum times
  `routed_scaling_factor`, `y = sum over chosen experts held here of w_i
  E_i(x) + E_shared(x)`.
- **One chip's share**: the configuration holds `n_routed_experts` experts
  of the published count (`deployment.experts_held = [first, last)`); the
  router keeps its published width; the reference, like the program, adds
  the held experts' parts and the shared expert and nothing for the experts
  held elsewhere. The vocabulary is the configuration's slice.

Departures from the published description, none with effect on the
mathematics: norm scales are stored as w with the layer multiplying by
(1 + w), the index key's LayerNorm too (its bias as it is); rotary pairs
are split halves, `harness/reference.py`'s convention; the vision tower,
the audio encoder and the multi-token-prediction block are not held. The
indexer and the router are float32 in the fp8 control too: both choose, and
a choice has no precision to lower. What the row does not settle is under
`assumed` in the configuration's file.

`reference_rows` runs one jitted program a layer, so that only one layer's
matrices are float32 at a time, a head at a time and the index scores in
blocks of `INDEX_ROWS` queries: it has to fit beside 8.2 GB of served
weights and 1.7 GB of cache at 12,416 positions. `dense=True` ignores the
selection (every causal position attended on full layers) and
`windowless=True` the window (every causal position on sliding layers).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import (F32, HIGHEST, _ident, _mm, _rms,
                                          _rope, fp8_round)

FULL, SLIDING = "full_attention", "sliding_attention"
# std of the seeded e_score_correction_bias: `glm_moe_dsa.py`'s, for its
# reason (at 256 sigmoid scores a larger one makes whole experts popular by
# the draw, and which of the held ones drew what sets the cell's rate)
BIAS_STD = 0.002
# std of the seeded index-key LayerNorm bias
INDEX_BIAS_STD = 0.1
# queries whose index scores exist together: (rows, heads, positions) f32
INDEX_ROWS = 128
# queries a head's scores exist for together: (rows, positions) f32
ATTN_ROWS = 1024


# ------------------------------------------------------------ sizes
@dataclasses.dataclass(frozen=True)
class Geo:
    """One kind of layer's latent attention."""
    heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v_head: int
    theta: float
    a_q: float
    a_kv: float

    @property
    def qk_head(self) -> int:
        return self.nope + self.rope

    @property
    def cache_row(self) -> int:
        """Numbers a position costs a layer of this kind in the cache."""
        return self.kv_lora + self.rope


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    kinds: tuple            # a layer's kind, FULL or SLIDING
    full: Geo
    sliding: Geo
    window: int
    gate: bool
    d_ff: int
    moe_ff: int
    experts: int            # of the whole layer, as published
    first_held: int
    held: int               # experts this chip holds
    shared: int
    top_k: int
    first_dense: int
    route_scale: float
    norm_topk: bool
    index_heads: int
    index_dim: int
    index_topk: int
    norm_eps: float
    index_norm_eps: float

    @property
    def layers(self) -> int:
        return len(self.kinds)

    @property
    def moe_layers(self) -> int:
        return max(0, self.layers - self.first_dense)

    def of_kind(self, kind: str) -> tuple:
        """Indices of the layers with routed experts ("E"), or of the
        layers whose attention is of `kind`."""
        if kind == "E":
            return tuple(range(self.first_dense, self.layers))
        return tuple(i for i, k in enumerate(self.kinds) if k == kind)

    def geo(self, kind: str) -> Geo:
        return self.sliding if kind == SLIDING else self.full


def sizes(cfg: dict) -> Sizes:
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing is not written down here")
    held = cfg["n_routed_experts"]
    published = cfg.get("published", {}).get("n_routed_experts", held)
    first, last = cfg.get("deployment", {}).get("experts_held", (0, held))
    if last - first != held or not 0 <= first <= published - held:
        raise ValueError(f"experts_held [{first}, {last}) of {published} "
                         f"and n_routed_experts {held} disagree")
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {FULL,
                                                               SLIDING}:
        raise ValueError(f"layer_types {kinds} and num_hidden_layers "
                         f"{cfg['num_hidden_layers']} disagree")
    hidden, rescale = cfg["hidden_size"], cfg["apply_mla_qkv_lora_rescale"]

    def geo(pre: str, heads: str) -> Geo:
        q_lora, kv_lora = cfg[pre + "q_lora_rank"], cfg[pre + "kv_lora_rank"]
        return Geo(
            heads=cfg[heads], q_lora=q_lora, kv_lora=kv_lora,
            nope=cfg[pre + "qk_nope_head_dim"],
            rope=cfg[pre + "qk_rope_head_dim"],
            v_head=cfg[pre + "v_head_dim"],
            theta=float(cfg[pre + "rope_theta"]),
            a_q=math.sqrt(hidden / q_lora) if rescale else 1.0,
            a_kv=math.sqrt(hidden / kv_lora) if rescale else 1.0)

    gates = {cfg["attention_gate_type"], cfg["swa_attention_gate_type"]}
    if gates - {"headwise"} and gates != {None}:
        raise ValueError(f"attention gates {gates}: a gate a head on both "
                         f"kinds, or none, is written down here")
    return Sizes(
        vocab=cfg["vocab_size"], d_model=hidden, kinds=kinds,
        full=geo("", "num_attention_heads"),
        sliding=geo("swa_", "swa_num_attention_heads"),
        window=cfg["sliding_window_size"], gate=gates == {"headwise"},
        d_ff=cfg["intermediate_size"],
        moe_ff=cfg["moe_intermediate_size"], experts=published,
        first_held=first, held=held, shared=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"],
        first_dense=cfg["first_k_dense_replace"],
        route_scale=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"],
        norm_eps=float(cfg["rms_norm_eps"]),
        index_norm_eps=float(cfg.get("assumed", {}).get("index_norm_eps",
                                                        1e-6)))


def tiny(cfg: dict) -> dict:
    """The same file at rehearsal size: control flow on the CPU, never a
    measurement. Every mechanism stays (a dense layer, two full and three
    sliding layers of unlike geometries, a gate a head, both scales, a
    share of the experts, an indexer whose choice is a quarter of the
    context, a window that is no multiple of a page)."""
    small = dict(cfg)
    small.update(hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=4, q_lora_rank=48, kv_lora_rank=96,
                 qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=32,
                 rope_theta=10000, swa_num_attention_heads=2,
                 swa_num_key_value_heads=2, swa_q_lora_rank=32,
                 swa_kv_lora_rank=112, swa_qk_nope_head_dim=32,
                 swa_qk_rope_head_dim=16, swa_v_head_dim=16,
                 swa_rope_theta=100, sliding_window_size=37,
                 intermediate_size=128, moe_intermediate_size=32,
                 n_routed_experts=4, num_experts_per_tok=2, vocab_size=512,
                 index_n_heads=16, index_head_dim=32, index_topk=64)
    small["published"] = {**cfg.get("published", {}), "n_routed_experts": 8}
    small["deployment"] = {**cfg.get("deployment", {}),
                           "experts_held": [0, 4], "min_prefill": 64}
    return small


# ------------------------------------------------------------ weights
def weight_shapes(s: Sizes) -> dict:
    """Normal weights of 0.02, output projections scaled down by depth, norm
    scales 0.1 around the identity, the router's bias `BIAS_STD`, the index
    key's LayerNorm bias `INDEX_BIAS_STD`; the per-layer layout the
    program's `SparseWindowMLAMoE` holds, the routed experts those held
    here, an indexer's leaves on the full layers alone."""
    e = s.d_model
    std = 0.02
    out_std = std / math.sqrt(2 * s.layers)

    def layer(i):
        g = s.geo(s.kinds[i])
        H = g.heads
        shapes = {
            "attn_norm": ((e,), 0.1),
            "wq_a": ((e, g.q_lora), std),
            "q_norm": ((g.q_lora,), 0.1),
            "wq_b": ((g.q_lora, H * g.qk_head), std),
            "wkv_a": ((e, g.kv_lora + g.rope), std),
            "kv_norm": ((g.kv_lora,), 0.1),
            "wkv_b": ((g.kv_lora, H * (g.nope + g.v_head)), std),
            "wo": ((H * g.v_head, e), out_std),
            "mlp_norm": ((e,), 0.1),
        }
        if s.gate:
            shapes["w_head_gate"] = ((e, H), std)
        if s.kinds[i] == FULL:
            shapes.update(
                wq_idx=((g.q_lora, s.index_heads * s.index_dim), std),
                wk_idx=((e, s.index_dim), std),
                k_idx_norm=((s.index_dim,), 0.1),
                k_idx_bias=((s.index_dim,), INDEX_BIAS_STD),
                w_idx=((e, s.index_heads), std))
        if i < s.first_dense:
            shapes.update(gate=((e, s.d_ff), std), up=((e, s.d_ff), std),
                          down=((s.d_ff, e), out_std))
            return shapes
        E, f, fs = s.held, s.moe_ff, s.moe_ff * s.shared
        shapes.update(
            router=((e, s.experts), std),
            router_bias=((s.experts,), BIAS_STD),
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
    """The program's SparseWindowMLAMoEConfig for this file."""
    from ray_tpu.models.sparse_window_mla_moe import SparseWindowMLAMoEConfig
    s = sizes(cfg)
    f, w = s.full, s.sliding
    dtype = cfg.get("torch_dtype", "bfloat16")
    return SparseWindowMLAMoEConfig(
        vocab_size=s.vocab, d_model=s.d_model, n_layers=s.layers,
        layer_types=s.kinds,
        n_heads=f.heads, q_lora_rank=f.q_lora, kv_lora_rank=f.kv_lora,
        qk_nope_head_dim=f.nope, qk_rope_head_dim=f.rope,
        v_head_dim=f.v_head, rope_theta=f.theta,
        swa_n_heads=w.heads, swa_q_lora_rank=w.q_lora,
        swa_kv_lora_rank=w.kv_lora, swa_qk_nope_head_dim=w.nope,
        swa_qk_rope_head_dim=w.rope, swa_v_head_dim=w.v_head,
        swa_rope_theta=w.theta, sliding_window=s.window,
        head_gate=s.gate,
        lora_rescale=bool(cfg["apply_mla_qkv_lora_rescale"]),
        d_ff=s.d_ff, moe_intermediate_size=s.moe_ff,
        n_routed_experts=s.experts, n_shared_experts=s.shared,
        experts_held=(s.first_held, s.held),
        num_experts_per_tok=s.top_k, first_k_dense_replace=s.first_dense,
        routed_scaling_factor=s.route_scale, norm_topk_prob=s.norm_topk,
        scoring_func=cfg.get("scoring_func", "sigmoid"),
        index_n_heads=s.index_heads, index_head_dim=s.index_dim,
        index_topk=s.index_topk, index_norm_eps=s.index_norm_eps,
        max_seq_len=max_seq_len, norm_eps=s.norm_eps,
        **{"dtype": dtype, "param_dtype": dtype, "min_prefill": int(
            cfg.get("deployment", {}).get("min_prefill", 0)), **extra})


def train_model(cfg: dict, seq_len: int):
    from ray_tpu.models.sparse_window_mla_moe import SparseWindowMLAMoE
    return SparseWindowMLAMoE(program_config(cfg, max_seq_len=seq_len))


# ------------------------------------------------------------ the reference
def _rope_leading(x, positions, g: Geo):
    """The rotary on the first `rope` numbers of x (n, heads, width)."""
    return jnp.concatenate(
        [_rope(x[..., :g.rope], positions, g.theta), x[..., g.rope:]],
        axis=-1)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * (1.0 + w) + b


def index_parts(s: Sizes, h, c_q, layer, positions):
    """(q^I (n, heads, width), k^I (n, width), w (n, heads)) of normed
    inputs h (n, d_model) and scaled query latents c_q (n, q_lora) of a
    full layer: float32, never rounded by the control."""
    n = h.shape[0]
    q = jnp.matmul(c_q, layer["wq_idx"], precision=HIGHEST).reshape(
        n, s.index_heads, s.index_dim)
    k = _layer_norm(jnp.matmul(h, layer["wk_idx"], precision=HIGHEST),
                    layer["k_idx_norm"], layer["k_idx_bias"],
                    s.index_norm_eps)
    w = jnp.matmul(h, layer["w_idx"], precision=HIGHEST) / math.sqrt(
        s.index_heads * s.index_dim)
    return (_rope_leading(q, positions, s.full),
            _rope_leading(k[:, None, :], positions, s.full)[:, 0], w)


def index_scores(q_idx, k_idx, w, rows_at, n_rows: int):
    """I[t, s] of queries t = rows_at .. rows_at + n_rows - 1 against every
    key, -inf where s > t: (n_rows, n) float32."""
    n = k_idx.shape[0]
    q = jax.lax.dynamic_slice_in_dim(q_idx, rows_at, n_rows)
    wt = jax.lax.dynamic_slice_in_dim(w, rows_at, n_rows)
    prod = jnp.einsum("thd,sd->ths", q, k_idx, precision=HIGHEST)
    scores = jnp.einsum("th,ths->ts", wt, jax.nn.relu(prod),
                        precision=HIGHEST)
    t = rows_at + jnp.arange(n_rows)
    return jnp.where(jnp.arange(n)[None, :] <= t[:, None], scores, -jnp.inf)


def _causal(n: int):
    return jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]


def selected_mask(s: Sizes, q_idx, k_idx, w, dense: bool = False):
    """bool (n, n): [t, s'] whether key s' is in query t's set `S_t`, the
    scores taken `INDEX_ROWS` queries at a time. `dense`: every causal
    position."""
    n = k_idx.shape[0]
    if dense or n <= s.index_topk:
        return _causal(n)
    rows = math.gcd(n, INDEX_ROWS)

    def block(at):
        scores = index_scores(q_idx, k_idx, w, at, rows)
        vals, idx = jax.lax.top_k(scores, s.index_topk)
        return jnp.zeros((rows, n), bool).at[
            jnp.arange(rows)[:, None], idx].max(vals > -jnp.inf)

    return jax.lax.map(block, jnp.arange(0, n, rows)).reshape(n, n)


def window_mask(n: int, window: int):
    """bool (n, n): [t, s'] whether 0 <= t - s' < window."""
    back = jnp.arange(n)[:, None] - jnp.arange(n)[None, :]
    return (back >= 0) & (back < window)


def _attention(s: Sizes, kind: str, h, layer, positions, quant, remat,
               dense=False, windowless=False):
    """MLA of the layer's kind on one sequence in the expanded form, each
    query's softmax over its set (full) or its window (sliding): h (n,
    d_model) f32."""
    g = s.geo(kind)
    n = h.shape[0]
    c_q = g.a_q * _rms(_mm(h, layer["wq_a"], quant), layer["q_norm"],
                       s.norm_eps)
    if kind == FULL:
        keep = selected_mask(s, *index_parts(s, h, c_q, layer, positions),
                             dense=dense)
    else:
        keep = _causal(n) if windowless else window_mask(n, s.window)
    kv_a = _mm(h, layer["wkv_a"], quant)
    c_kv = g.a_kv * _rms(kv_a[:, :g.kv_lora], layer["kv_norm"], s.norm_eps)
    k_rope = _rope(kv_a[:, None, g.kv_lora:], positions, g.theta)[:, 0]
    gates = (jax.nn.sigmoid(jnp.matmul(h, layer["w_head_gate"],
                                       precision=HIGHEST))
             if s.gate else jnp.ones((n, g.heads), F32))
    rows = math.gcd(n, ATTN_ROWS)

    def one_head(acc, w):
        """One head at a time, its weights cut out of the layer's, a block
        of queries at a time: the (seq, seq) scores of all heads never
        exist together."""
        wq, wkv, wo, gate = w
        q = _mm(c_q, wq, quant)
        q = jnp.concatenate(
            [q[:, :g.nope],
             _rope(q[:, None, g.nope:], positions, g.theta)[:, 0]], axis=-1)
        kv = _mm(c_kv, wkv, quant)
        k = quant(jnp.concatenate([kv[:, :g.nope], k_rope], axis=-1))
        v = quant(kv[:, g.nope:])

        def block(at):
            qb = jax.lax.dynamic_slice_in_dim(q, at, rows)
            kb = jax.lax.dynamic_slice_in_dim(keep, at, rows)
            scores = jnp.einsum("qd,kd->qk", quant(qb), k,
                                precision=HIGHEST) / (g.qk_head ** 0.5)
            probs = jax.nn.softmax(jnp.where(kb, scores, -jnp.inf), axis=-1)
            return jnp.einsum("qk,kd->qd", quant(probs), v,
                              precision=HIGHEST)

        out = jax.lax.map(block, jnp.arange(0, n, rows)).reshape(
            n, g.v_head)
        return acc + _mm(out * gate[:, None], wo, quant), None

    if remat:
        one_head = jax.checkpoint(one_head)
    by_head = (
        layer["wq_b"].reshape(g.q_lora, g.heads, g.qk_head).transpose(
            1, 0, 2),
        layer["wkv_b"].reshape(g.kv_lora, g.heads,
                               g.nope + g.v_head).transpose(1, 0, 2),
        layer["wo"].reshape(g.heads, g.v_head, s.d_model),
        gates.T)
    out, _ = jax.lax.scan(one_head, jnp.zeros_like(h), by_head)
    return out


def route(s: Sizes, h, layer):
    """(experts (n, k), weights (n, k)) of tokens h (n, d_model) over all
    the published experts, float32 throughout and never rounded by the
    control: the bias moves the choice, the weight is the score alone."""
    scores = jax.nn.sigmoid(jnp.matmul(h, layer["router"].astype(F32),
                                       precision=HIGHEST))
    _, top_e = jax.lax.top_k(scores + layer["router_bias"].astype(F32),
                             s.top_k)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if s.norm_topk:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    return top_e, top_w * s.route_scale


def _swiglu(h, gate, up, down, quant):
    """`ATTN_ROWS` tokens at a time: (rows, d_ff) and not (n, d_ff) exist.
    (The control's per-tensor scale is then a block's, as a layer's input
    is a sequence's: a finer rounding, never a coarser one.)"""
    n = h.shape[0]
    rows = math.gcd(n, ATTN_ROWS)

    def block(hb):
        return _mm(jax.nn.silu(_mm(hb, gate, quant)) * _mm(hb, up, quant),
                   down, quant)

    return jax.lax.map(block, h.reshape(n // rows, rows, -1)).reshape(
        n, -1)


def routed_part(s: Sizes, h, layer, quant, first: int = None):
    """sum over the chosen experts held here of w_i E_i(h): the held
    experts (`first` ..: the matrices given) walked one by one, each lifted
    to float32 alone, a token's weight zero for an expert it did not
    choose; what an expert held elsewhere would add is left out."""
    n = h.shape[0]
    first = s.first_held if first is None else first
    top_e, top_w = route(s, h, layer)
    weight = jnp.zeros((n, s.experts), F32).at[
        jnp.arange(n)[:, None], top_e].add(top_w)
    held = layer["moe_gate"].shape[0]
    weight = jax.lax.dynamic_slice_in_dim(weight, first, held, axis=1)

    def one(acc, ew):
        gate, up, down, w = ew
        y = _swiglu(h, gate.astype(F32), up.astype(F32), down.astype(F32),
                    quant)
        return acc + w[:, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (layer["moe_gate"], layer["moe_up"],
                         layer["moe_down"], weight.T))
    return y


def shared_part(h, layer, quant):
    return _swiglu(h, layer["shared_gate"].astype(F32),
                   layer["shared_up"].astype(F32),
                   layer["shared_down"].astype(F32), quant)


_BIG = ("moe_gate", "moe_up", "moe_down")


def _block(s: Sizes, kind: str, x, layer, positions, quant, remat=False,
           dense=False, windowless=False):
    """One layer of `kind` on one sequence: x (seq, d_model) f32."""
    small = {k: (v if k in _BIG else v.astype(F32))
             for k, v in layer.items()}
    h = _rms(x, small["attn_norm"], s.norm_eps)
    x = x + _attention(s, kind, h, small, positions, quant, remat, dense,
                       windowless)
    h = _rms(x, small["mlp_norm"], s.norm_eps)
    if "router" in layer:
        return x + routed_part(s, h, small, quant) + shared_part(h, small,
                                                                 quant)
    return x + _swiglu(h, small["gate"], small["up"], small["down"], quant)


def _head(s: Sizes, x, norm, head, quant, window=None):
    if window is not None:
        x = jax.lax.dynamic_slice_in_dim(x, window[0], window[1], axis=0)
    x = _rms(x, norm.astype(F32), s.norm_eps)
    return _mm(x, head.astype(F32), quant)


def logits_fn(s: Sizes, params, tokens, quant=_ident, window=None,
              remat=False, dense=False, windowless=False):
    """tokens (seq,) int32 -> logits (seq, vocab) f32 of one sequence, or
    of the `window` = (start, rows) of its positions."""
    positions = jnp.arange(tokens.shape[0])
    x = params["embed"].astype(F32)[tokens]
    for kind, layer in zip(s.kinds, params["layers"]):
        block = functools.partial(
            _block, s, kind, positions=positions, quant=quant, remat=remat,
            dense=dense, windowless=windowless)
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
    lambda s, kind, x, layer, positions, control, dense, windowless: _block(
        s, kind, x, layer, positions, _QUANT[control], dense=dense,
        windowless=windowless),
    static_argnums=(0, 1, 5, 6, 7))
_jit_head = jax.jit(
    lambda s, x, norm, head, start, rows, control: _head(
        s, x, norm, head, _QUANT[control], (start, rows)),
    static_argnums=(0, 5, 6))


def reference_rows(s: Sizes, params, tokens, start, rows: int,
                   control: bool = False, dense: bool = False,
                   windowless: bool = False):
    """Logits of positions start .. start + rows - 1 of one sequence that
    is padded at its end (every layer is causal, a query's set and its
    window hold no later position and a token's experts are its own, so the
    padding touches nothing before it). `control` rounds every matmul
    operand to fp8 instead; the indexer and the routing stay float32 in
    both. `dense` ignores the indexer's choice, `windowless` the window.
    One jitted program a layer."""
    positions = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens].astype(F32)
    for kind, layer in zip(s.kinds, params["layers"]):
        x = _jit_block(s, kind, x, layer, positions, control, dense,
                       windowless)
    return _jit_head(s, x, params["final_norm"], params["lm_head"], start,
                     rows, control)


# ------------------------------------------------------------ required ops
def _attn_params(s: Sizes, g: Geo) -> int:
    return (s.d_model * g.q_lora + g.q_lora * g.heads * g.qk_head
            + s.d_model * (g.kv_lora + g.rope)
            + g.kv_lora * g.heads * (g.nope + g.v_head)
            + g.heads * g.v_head * s.d_model
            + (s.d_model * g.heads if s.gate else 0))


def _index_params(s: Sizes) -> int:
    return (s.full.q_lora * s.index_heads * s.index_dim
            + s.d_model * (s.index_dim + s.index_heads))


def matmul_params(s: Sizes) -> float:
    """Parameters that multiply a token's activations on this chip: every
    layer's attention projections and gate, a full layer's indexer, the
    dense layers' feed-forward, and in an expert layer the router at its
    whole width, the shared experts and of the routed experts the `top_k *
    held / experts` a token's choices give this share when the routing is
    even; the output head. Not the embedding table, not the norms."""
    dense = 3 * s.d_model * s.d_ff
    moe = (s.d_model * s.experts
           + (s.top_k * s.held / s.experts + s.shared)
           * 3 * s.d_model * s.moe_ff)
    n_full, n_sliding = len(s.of_kind(FULL)), len(s.of_kind(SLIDING))
    return (n_full * (_attn_params(s, s.full) + _index_params(s))
            + n_sliding * _attn_params(s, s.sliding)
            + s.first_dense * dense + s.moe_layers * moe
            + s.d_model * s.vocab)


def _keys_seen(tokens: int, reach: int) -> float:
    """Sum over queries 0 .. tokens - 1 of min(position + 1, reach)."""
    k = min(tokens, reach)
    return k * (k + 1) / 2.0 + (tokens - k) * k


def attention_flops_per_token(s: Sizes, seq_len: int,
                              passes: int = 3) -> float:
    """A full layer: the indexer's scores over every causal position (2 x
    index_heads x index_dim operations a position) and the main attention
    over the `min(position + 1, index_topk)` chosen; a sliding layer: the
    `min(position + 1, window)` positions of the window (QK^T 2 x (nope +
    rope) and PV 2 x v a head), per token at the mean over a sequence of
    `seq_len`; the backward is twice that (`passes` 3)."""
    f, w = s.full, s.sliding
    index = 2.0 * s.index_heads * s.index_dim * (seq_len + 1) / 2.0
    full = 2.0 * f.heads * (f.qk_head + f.v_head) * _keys_seen(
        seq_len, s.index_topk) / seq_len
    sliding = 2.0 * w.heads * (w.qk_head + w.v_head) * _keys_seen(
        seq_len, s.window) / seq_len
    return passes * (len(s.of_kind(FULL)) * (index + full)
                     + len(s.of_kind(SLIDING)) * sliding)


def train_flops_per_token(s: Sizes, seq_len: int) -> float:
    """Forward + backward: 6 per matmul parameter plus the attentions."""
    return 6.0 * matmul_params(s) + attention_flops_per_token(s, seq_len)


def window_latent_decode_call(s: Sizes, window_positions: int,
                              lane_layers: int, itemsize: int = 2) -> dict:
    """The sliding layers' absorbed decode attention, as the algorithm
    needs it: `window_positions` rows (summed over lanes, sliding layers
    and steps: min(length, window) a lane and layer) of `kv_lora + rope`
    numbers (1,088: 2,176 B) read once and used as key and as value; a
    lane's queries in and latent outputs out a layer (`lane_layers` = lanes
    x sliding layers, summed over steps); scores are 2 x (kv_lora + rope)
    and the output 2 x kv_lora operations a head and row. What the ring's
    first and last page hold outside the window and a row's padding to
    whole lanes (1,152), which the kernel copies too, do not count."""
    g = s.sliding
    rows = window_positions * g.cache_row * itemsize
    q_and_o = lane_layers * g.heads * (g.cache_row + g.kv_lora) * itemsize
    return {"flops": 2.0 * g.heads * (g.cache_row + g.kv_lora)
            * window_positions,
            "bytes": float(rows + q_and_o)}


def flash_prefill_call(s: Sizes, tokens: int, kind: str = FULL,
                       itemsize: int = 2) -> dict:
    """The flash forward of one prefill of `tokens` true tokens over the
    layers of `kind`, in the expanded form at the kind's true widths: each
    query's keys (`min(position + 1, index_topk)` chosen on a full layer,
    `min(position + 1, window)` on a sliding one) at QK^T 2 x (nope +
    rope) and PV 2 x v operations a head; queries and keys in at `nope +
    rope` numbers a head and token, values in and outputs out at `v`. The
    padding of a prompt to its bucket, what a block holds outside a window
    or a set and a value padded to its key's width, which the kernels
    compute and mask, do not count."""
    g = s.geo(kind)
    keys = _keys_seen(tokens, s.window if kind == SLIDING else s.index_topk)
    layers = len(s.of_kind(kind))
    flops = 2.0 * g.heads * (g.qk_head + g.v_head) * keys
    nbytes = tokens * g.heads * (2 * g.qk_head + 2 * g.v_head) * itemsize
    return {"flops": layers * flops, "bytes": float(layers * nbytes)}


def flash_window_call(s: Sizes, tokens: int, itemsize: int = 2) -> dict:
    """One prefill's windowed flash forward over all sliding layers
    (`kernel.flash_window_roofline.mixed8k` reads it under this name)."""
    return flash_prefill_call(s, tokens, SLIDING, itemsize)


def dsa_index_call(s: Sizes, live_positions: int, lanes: int,
                   itemsize: int = 2) -> dict:
    """The indexer's scores of a decode step, all full layers, as the
    algorithm needs them, for one step or (the counts being sums) for
    many: every live position's index key (`index_dim` numbers) read once
    a full layer, a lane's index queries and head weights in and its scores
    out; 2 x index_heads x index_dim operations a position."""
    layers = len(s.of_kind(FULL))
    keys = live_positions * s.index_dim * itemsize
    q_and_w = lanes * s.index_heads * (s.index_dim * itemsize + 4)
    scores = live_positions * 4
    return {"flops": 2.0 * s.index_heads * s.index_dim * live_positions
            * layers,
            "bytes": float(layers * (keys + q_and_w + scores))}


def dsa_attend_call(s: Sizes, selected_positions: int, lane_layers: int,
                    itemsize: int = 2) -> dict:
    """The full layers' absorbed attention over the chosen rows, as the
    algorithm needs it: `selected_positions` rows (summed over lanes, full
    layers and steps) of `kv_lora + rope` numbers read once and used as key
    and as value; a lane's queries in and latent outputs out a full layer.
    `lane_layers` comes as lanes x all layers (the reader's
    `kernel.dsa_attend_roofline.code16k` knows one kind of layer) and is
    cut here to the full layers' share. The reader's time is `r.attn_core`
    of a step, which in this class holds the sliding layers' ring walks
    too: their rows are not counted here, so the share reads low by their
    time (`kernel.mla_window_decode_roofline.notes12k` has them)."""
    g = s.full
    lane_layers = lane_layers * len(s.of_kind(FULL)) / float(s.layers)
    rows = selected_positions * g.cache_row * itemsize
    q_and_o = lane_layers * g.heads * (g.cache_row + g.kv_lora) * itemsize
    return {"flops": 2.0 * g.heads * (g.cache_row + g.kv_lora)
            * selected_positions,
            "bytes": float(rows + q_and_o)}


def dsa_prefill_call(s: Sizes, tokens: int, itemsize: int = 2) -> dict:
    """Index scores plus attention of one prefill of `tokens` true tokens,
    as the algorithm needs them, all layers: on a full layer the causal
    half of `tokens^2 x index_heads x index_dim x 2` operations for the
    scores and each query's chosen keys, on a sliding layer each query's
    window (`flash_prefill_call`, both kinds: the reader's time is
    `r.attn_index` and `r.attn_core` of a prefill, which hold both)."""
    layers = len(s.of_kind(FULL))
    index_flops = (2.0 * s.index_heads * s.index_dim * tokens
                   * (tokens + 1) / 2.0)
    index_bytes = (tokens * (s.index_heads * s.index_dim + s.index_dim)
                   * itemsize + tokens * s.index_heads * 4)
    full = flash_prefill_call(s, tokens, FULL, itemsize)
    sliding = flash_prefill_call(s, tokens, SLIDING, itemsize)
    return {"flops": layers * index_flops + full["flops"]
            + sliding["flops"],
            "bytes": float(layers * index_bytes + full["bytes"]
                           + sliding["bytes"])}


def moe_gmm_call(s: Sizes, pairs: int, experts_touched: int,
                 itemsize: int = 2) -> dict:
    """The routed experts' three grouped matmuls, as the algorithm needs
    them, `pairs` (token, held expert) pairs and `experts_touched` held
    experts with at least one pair, both summed over layers and steps: the
    three matrices of each touched expert read once, each pair's
    activation in and result out; 6 * d_model * moe_ff operations a pair."""
    weights = experts_touched * 3 * s.d_model * s.moe_ff * itemsize
    acts = pairs * 2 * s.d_model * itemsize
    return {"flops": 6.0 * s.d_model * s.moe_ff * pairs,
            "bytes": float(weights + acts)}
