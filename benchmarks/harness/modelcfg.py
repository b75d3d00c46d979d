"""A configuration file (`benchmarks/configs/<name>.json`, published key
names) read into the sizes the yardstick needs, and into the program's own
`TransformerConfig` for the system under test."""
from __future__ import annotations

import dataclasses
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


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


def program_config(cfg: dict, max_seq_len: int, **extra):
    """The program's TransformerConfig for this file. The program derives
    head_dim as d_model / n_heads, which must agree with the file."""
    from ray_tpu.models.config import TransformerConfig
    s = sizes(cfg)
    if s.d_model != s.heads * s.head_dim:
        raise ValueError("the program cannot hold head_dim * heads != hidden")
    dtype = cfg.get("torch_dtype", "bfloat16")
    return TransformerConfig(
        vocab_size=s.vocab, d_model=s.d_model, n_layers=s.layers,
        n_heads=s.heads, n_kv_heads=s.kv_heads, d_ff=s.d_ff,
        max_seq_len=max_seq_len, rope_theta=s.rope_theta,
        norm_eps=s.norm_eps, tie_embeddings=s.tied, dtype=dtype,
        param_dtype=dtype, **extra)
