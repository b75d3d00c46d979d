"""Transformer configuration + presets.

Presets cover the reference's LLM workloads (Llama-2-7B fine-tune is the
headline release test, reference release/release_tests.yaml:963-1010) and
small debug models for CI.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


class ConfigDtypes:
    """A config's `dtype` and `param_dtype` as dtypes."""

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def parameter_dtype(self):
        return jnp.dtype(self.param_dtype)


@dataclasses.dataclass(frozen=True)
class TransformerConfig(ConfigDtypes):
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: Optional[int] = None      # None = MHA
    d_ff: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"               # activation/compute dtype
    param_dtype: str = "float32"
    remat: bool = True                    # checkpoint each layer in scan
    # What a checkpointed layer keeps for its backward beside its input:
    # a prefix of `transformer.REMAT_LADDER`, cheapest rung first. Bytes a
    # token and layer, a = bytes of an activation (2 in bf16; the figure
    # in brackets is Mistral-7B's in bf16), h = n_heads * head_dim, kv =
    # kv_heads * head_dim:
    #   "full"                nothing: the backward runs the layer's
    #                         forward again (less `down`). 0.
    #   "save_attn"           flash attention's output and log-sum-exp:
    #                         the forward kernel runs once.
    #                         a h + 4 n_heads (8,320).
    #   "save_attn_qkv"       + q, k and v as the kernel takes them: no
    #                         second projection, rotation or transpose.
    #                         + a (h + 2 kv) (20,608).
    #   "save_attn_stream"    + the stream after attention: no second
    #                         `attn @ wo`. + a d_model (28,800). A MoE
    #                         layer's ladder ends here.
    #   "save_attn_stream_up" + the MLP's `h @ up`. + a d_ff (57,472).
    #   "save_matmuls"        + `h @ gate`: what the backward still runs
    #                         again is bound by bytes and cheap (two
    #                         rms_norm, silu(gate) * up, the residual
    #                         additions). + a d_ff (86,144).
    # "auto" takes, when a step is traced, the dearest rung whose kept
    # bytes a device (tokens a device x bytes above x n_layers) fit
    # `transformer.REMAT_KEPT_BYTES_BUDGET`, a quarter of a v5e's 16 GiB,
    # and never less than "save_attn_qkv". `Transformer.remat_plan(tokens)`
    # says which rung that is and what it keeps; a named rung is taken as
    # named. Whoever trains at memory's edge names a rung.
    remat_policy: str = "auto"
    use_ring_attention: bool = False      # seq-parallel attention (sp axis)
    # >0 with a pp>1 mesh: run the layer stack as a GPipe microbatch
    # pipeline over the pp axis (parallel/pipeline.py). Bubble fraction
    # is (pp-1)/(M+pp-1) — pick M >= 4*pp.
    pipeline_microbatches: int = 0
    # The training step's flash blocks (`Transformer._layer`, forward and
    # backward). Serving's prefill does not read them: `models/decode.py`
    # gives the forward `models.gqa.FULL_BLOCKS` cut to its bucket.
    attn_block_q: int = 128
    attn_block_k: int = 128
    loss_chunk: int = 0                   # >0: chunked LM loss (seq chunks)
    # --- Mixture of Experts (0 = dense FFN). Experts shard over the ep
    # mesh axis; see models/moe.py for dispatch semantics.
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01            # load-balance loss weight

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def _ffn_params(self) -> int:
        e, f = self.d_model, self.d_ff
        if not self.moe_num_experts:
            return 3 * e * f
        return self.moe_num_experts * (3 * e * f + e)       # + router

    def num_params(self) -> int:
        """Parameter count (embeddings + layers + head)."""
        e, hd = self.d_model, self.head_dim
        per_layer = (e * self.n_heads * hd          # wq
                     + 2 * e * self.kv_heads * hd   # wk, wv
                     + self.n_heads * hd * e        # wo
                     + self._ffn_params()
                     + 2 * e)                       # two norms
        total = self.vocab_size * e + self.n_layers * per_layer + e
        if not self.tie_embeddings:
            total += e * self.vocab_size
        return total


def tiny(vocab_size: int = 256) -> TransformerConfig:
    """CI/debug model: runs on the 8-device CPU mesh in seconds."""
    return TransformerConfig(
        vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=128, max_seq_len=128, remat=False,
        dtype="float32", param_dtype="float32")


def bench_1b() -> TransformerConfig:
    """The ~1B dense decoder `chip_smoke.py` runs, sized for one 16 GB
    v5e chip: 953M parameters, bf16 weights and bf16 Adam state, no remat
    at batch 2 x 2048, 1024-blocks for the flash kernels. Invented widths:
    Llama-shaped, but no published model has them."""
    return TransformerConfig(
        vocab_size=32000, d_model=2048, n_layers=16, n_heads=16,
        n_kv_heads=16, d_ff=5632, max_seq_len=2048, remat=False,
        dtype="bfloat16", param_dtype="bfloat16", loss_chunk=0,
        attn_block_q=1024, attn_block_k=1024)


def llama2_7b() -> TransformerConfig:
    return TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=None, d_ff=11008, max_seq_len=4096)


def llama2_13b() -> TransformerConfig:
    return TransformerConfig(
        vocab_size=32000, d_model=5120, n_layers=40, n_heads=40,
        n_kv_heads=None, d_ff=13824, max_seq_len=4096)


def llama3_8b() -> TransformerConfig:
    return TransformerConfig(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq_len=8192, rope_theta=500000.0)


PRESETS = {
    "tiny": tiny,
    "bench-1b": bench_1b,
    "llama2-7b": llama2_7b,
    "llama2-13b": llama2_13b,
    "llama3-8b": llama3_8b,
}
