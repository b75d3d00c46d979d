"""A second architecture, for `tests/test_models.py` alone: it is placed in
a copy of the benchmark beside `dense_gqa.py`, with a configuration that
names it, to show that the harness asks the module and assumes nothing.
Deliberately another shape of `Sizes` (no `kv_heads`, no `d_ff`, no
`head_dim`: every head has keys of its own and the feed-forward is a
multiple of the width) and another tree (tied embeddings: no `lm_head`).
It maps onto the program's `TransformerConfig`, and its tree has the layout
the program's `Transformer` holds: the engine serves the benchmark's own
arrays, one copy on the chip, so a tree the program cannot hold cannot be
served. The interface is `benchmarks/models/dense_gqa.py`'s."""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import (F32, HIGHEST, _ident, _mm, _rms,
                                          _rope, fp8_round)


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    width: int
    depth: int
    heads: int
    ffn_mult: int
    theta: float
    eps: float

    @property
    def ffn(self) -> int:
        return self.ffn_mult * self.width


def sizes(cfg: dict) -> Sizes:
    return Sizes(vocab=cfg["vocab_size"], width=cfg["n_embd"],
                 depth=cfg["n_layer"], heads=cfg["n_head"],
                 ffn_mult=cfg["ffn_mult"], theta=float(cfg["rope_theta"]),
                 eps=float(cfg["rms_norm_eps"]))


def tiny(cfg: dict) -> dict:
    return dict(cfg, vocab_size=256, n_embd=48, n_layer=3, n_head=3)


def weight_shapes(s: Sizes) -> dict:
    L, e, f, std = s.depth, s.width, s.ffn, 0.02
    out = std / math.sqrt(2 * L)
    return {"embed": ((s.vocab, e), std), "final_norm": ((e,), 0.1),
            "layers": {"attn_norm": ((L, e), 0.1), "mlp_norm": ((L, e), 0.1),
                       "wq": ((L, e, e), std), "wk": ((L, e, e), std),
                       "wv": ((L, e, e), std), "wo": ((L, e, e), out),
                       "gate": ((L, e, f), std), "up": ((L, e, f), std),
                       "down": ((L, f, e), out)}}


def param_count(s: Sizes) -> int:
    from benchmarks.harness import weights
    return weights.param_count(weight_shapes(s))


def program_config(cfg: dict, max_seq_len: int, **extra):
    from ray_tpu.models.config import TransformerConfig
    s = sizes(cfg)
    return TransformerConfig(
        vocab_size=s.vocab, d_model=s.width, n_layers=s.depth,
        n_heads=s.heads, n_kv_heads=None, d_ff=s.ffn,
        max_seq_len=max_seq_len, rope_theta=s.theta, norm_eps=s.eps,
        tie_embeddings=True, dtype="bfloat16", param_dtype="bfloat16",
        **{"remat": False, **extra})


def train_model(cfg: dict, seq_len: int):
    from ray_tpu.models import Transformer
    return Transformer(program_config(cfg, seq_len, loss_chunk=0))


def logits_fn(s: Sizes, params, tokens, quant=_ident, window=None,
              remat=False):
    n, hd = tokens.shape[0], s.width // s.heads
    positions = jnp.arange(n)
    causal = positions[:, None] >= positions[None, :]

    def layer(x, w):
        w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
        h = _rms(x, w["attn_norm"], s.eps)
        q, k, v = (_mm(h, w[m], quant).reshape(n, s.heads, hd)
                   for m in ("wq", "wk", "wv"))
        q, k = _rope(q, positions, s.theta), _rope(k, positions, s.theta)
        scores = jnp.einsum("qhd,khd->hqk", quant(q), quant(k),
                            precision=HIGHEST) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        attn = jnp.einsum("hqk,khd->qhd", quant(probs), quant(v),
                          precision=HIGHEST).reshape(n, s.width)
        x = x + _mm(attn, w["wo"], quant)
        h = _rms(x, w["mlp_norm"], s.eps)
        mid = jax.nn.silu(_mm(h, w["gate"], quant)) * _mm(h, w["up"], quant)
        return x + _mm(mid, w["down"], quant), None

    if remat:
        layer = jax.checkpoint(layer)
    x, _ = jax.lax.scan(layer, params["embed"].astype(F32)[tokens],
                        params["layers"])
    if window is not None:
        x = jax.lax.dynamic_slice_in_dim(x, window[0], window[1], axis=0)
    x = _rms(x, params["final_norm"].astype(F32), s.eps)
    return _mm(x, params["embed"].T.astype(F32), quant)


def loss_fn(s: Sizes, params, tokens, quant=_ident, remat=False):
    logp = jax.nn.log_softmax(
        logits_fn(s, params, tokens, quant, remat=remat)[:-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], -1))


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def reference_rows(s: Sizes, params, tokens, start, rows: int,
                   control: bool = False):
    return logits_fn(s, params, tokens, fp8_round if control else _ident,
                     window=(start, rows))


def matmul_params(s: Sizes) -> int:
    return (s.depth * (4 * s.width ** 2 + 3 * s.width * s.ffn)
            + s.width * s.vocab)


def attention_flops_per_token(s: Sizes, seq_len: int,
                              passes: int = 3) -> float:
    return passes * 2.0 * seq_len * s.width * s.depth


def train_flops_per_token(s: Sizes, seq_len: int) -> float:
    return 6.0 * matmul_params(s) + attention_flops_per_token(s, seq_len)
