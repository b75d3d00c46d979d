"""Everything the yardstick knows of one architecture: the dense decoder
with RMSNorm, grouped-query attention under rotary positions and a SwiGLU
feed-forward, every layer alike (InternLM2, Mistral, Llama).

A configuration names its model (`"model": "dense_gqa"`) and
`harness/modelcfg.load_model` imports this file by path. The harness asks
the module and assumes nothing of the architecture; of a model's sizes it
reads `vocab` alone. **The interface** the next architecture's file
implements (`harness/modelcfg.INTERFACE` lists the names, and a module
without one of them is refused when it is loaded):

- `Sizes`, `sizes(cfg)`: the configuration file (published key names) as
  one frozen, hashable value; it is what every function below takes as
  `s`, and a static argument of jitted programs. It has `vocab`; every
  other field is the module's own, read only here and by the metrics that
  are listed for cells of this model.
- `tiny(cfg)`: the same file at rehearsal size (the CPU, control flow only).
- `weight_shapes(s)`: the tree of `(shape, std)` that
  `harness/weights.make_weights` fills from the seed. The program and the
  reference get the same arrays, so the tree has the layout the program
  holds. A cut to a chip's share of the experts or the vocabulary is stated
  in the configuration and honoured here and in the reference alike.
- `program_config(cfg, max_seq_len, **extra)`: what `LLMEngine(model=...)`
  takes for the system under test; `train_model(cfg, seq_len)`: the
  program's object whose `loss(params, {"tokens": (batch, seq)})` the train
  step differentiates (another class of the program's than `Transformer`,
  where the architecture needs one).
- the plain reference, float32 `jax.numpy` at precision `highest`, no
  kernel, cache or batching trick, importing nothing of the program:
  `logits_fn(s, params, tokens, quant, window, remat)`,
  `loss_fn(s, params, tokens, quant, remat)` and the jitted
  `reference_rows(s, params, tokens, start, rows, control)`. `quant` puts
  the control in the reference's place: every matmul operand (weights,
  activations, keys and values) is rounded to the precision below the
  configuration's (`harness.reference.fp8_round`) before use.
- the operations the algorithm requires, from shapes alone:
  `matmul_params(s)`, `attention_flops_per_token(s, seq_len, passes)`,
  `train_flops_per_token(s, seq_len)`, `param_count(s)`. What the program
  recomputes (remat, the two-kernel backward's second QK^T and dP) does
  not count, and neither does the embedding lookup, a gather.

Departures from the published descriptions, both without effect on the
mathematics: norm scales are stored as w with the layer multiplying by
(1 + w); InternLM2's fused wqkv is held as three matrices.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import (F32, HIGHEST, _ident, _mm, _rms,
                                          _rope, fp8_round)


# ------------------------------------------------------------ sizes
@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float
    norm_eps: float
    tied: bool

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


def sizes(cfg: dict) -> Sizes:
    return Sizes(vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
                 layers=cfg["num_hidden_layers"],
                 heads=cfg["num_attention_heads"],
                 kv_heads=cfg["num_key_value_heads"],
                 head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
                 rope_theta=float(cfg["rope_theta"]),
                 norm_eps=float(cfg["rms_norm_eps"]),
                 tied=bool(cfg["tie_word_embeddings"]))


def tiny(cfg: dict) -> dict:
    """The same file at rehearsal size: control flow on the CPU, never a
    measurement. Ratios of heads stay; every width shrinks."""
    small = dict(cfg)
    small.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, intermediate_size=128,
                 vocab_size=512)
    return small


# ------------------------------------------------------------ weights
def weight_shapes(s: Sizes) -> dict:
    """Normal weights of 0.02, output projections scaled down by depth, norm
    scales 0.1 around the identity; the layout the program's `Transformer`
    holds (stacked layers)."""
    L, e, f = s.layers, s.d_model, s.d_ff
    std = 0.02
    out_std = std / math.sqrt(2 * L)
    shapes = {
        "embed": ((s.vocab, e), std),
        "final_norm": ((e,), 0.1),
        "layers": {
            "attn_norm": ((L, e), 0.1),
            "wq": ((L, e, s.q_dim), std),
            "wk": ((L, e, s.kv_dim), std),
            "wv": ((L, e, s.kv_dim), std),
            "wo": ((L, s.q_dim, e), out_std),
            "mlp_norm": ((L, e), 0.1),
            "gate": ((L, e, f), std),
            "up": ((L, e, f), std),
            "down": ((L, f, e), out_std),
        },
    }
    if not s.tied:
        shapes["lm_head"] = ((e, s.vocab), std)
    return shapes


def param_count(s: Sizes) -> int:
    from benchmarks.harness import weights
    return weights.param_count(weight_shapes(s))


# ------------------------------------------------------------ the program
def program_config(cfg: dict, max_seq_len: int, **extra):
    """The program's TransformerConfig for this file, with the attention
    blocks its deployment states. The program derives head_dim as
    d_model / n_heads, which must agree with the file."""
    from ray_tpu.models.config import TransformerConfig
    s = sizes(cfg)
    if s.d_model != s.heads * s.head_dim:
        raise ValueError("the program cannot hold head_dim * heads != hidden")
    dtype = cfg.get("torch_dtype", "bfloat16")
    dep = cfg.get("deployment", {})
    blocks = {k: dep[k] for k in ("attn_block_q", "attn_block_k") if k in dep}
    return TransformerConfig(
        vocab_size=s.vocab, d_model=s.d_model, n_layers=s.layers,
        n_heads=s.heads, n_kv_heads=s.kv_heads, d_ff=s.d_ff,
        max_seq_len=max_seq_len, rope_theta=s.rope_theta,
        norm_eps=s.norm_eps, tie_embeddings=s.tied, dtype=dtype,
        param_dtype=dtype, **{"remat": False, **blocks, **extra})


def train_model(cfg: dict, seq_len: int):
    from ray_tpu.models import Transformer
    remat = bool(cfg["deployment"].get("remat", False))
    return Transformer(program_config(cfg, max_seq_len=seq_len, remat=remat,
                                      loss_chunk=0))


# ------------------------------------------------------------ the reference
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
    of the `window` = (start, rows) of its positions. The layers are walked
    by a scan that lifts one layer's weights to f32 at a time, so the f32
    copy of a whole model never exists."""
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


# ------------------------------------------------------------ required ops
def matmul_params(s: Sizes) -> int:
    """Parameters that multiply activations: every layer's projections and
    MLP, and the output head. Not the embedding table, not the norms."""
    per_layer = (s.d_model * s.q_dim + 2 * s.d_model * s.kv_dim
                 + s.q_dim * s.d_model + 3 * s.d_model * s.d_ff)
    return s.layers * per_layer + s.d_model * s.vocab


def attention_flops_per_token(s: Sizes, seq_len: int,
                              passes: int = 3) -> float:
    """Causal attention per token and layer: QK^T and PV are 2 * seq * q_dim
    multiply-adds each over the causal half, so 2 * seq * q_dim operations
    forward; the backward is twice that (`passes` 3 = forward + backward)."""
    return passes * 2.0 * seq_len * s.q_dim * s.layers


def train_flops_per_token(s: Sizes, seq_len: int) -> float:
    """Forward + backward: 6 per matmul parameter plus causal attention."""
    return 6.0 * matmul_params(s) + attention_flops_per_token(s, seq_len)
