"""Operations and bytes the algorithms require, from shapes alone. What the
program recomputes (remat, the two-kernel backward's second QK^T and dP)
does not count, and neither does the embedding lookup, a gather."""
from __future__ import annotations

from benchmarks.harness.modelcfg import Sizes


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


def flash_call(batch: int, heads: int, kv_heads: int, seq: int,
               head_dim: int, itemsize: int = 2) -> dict:
    """One layer's causal flash attention, forward and backward, as the
    algorithm needs it: 2 matmuls forward and 5 backward (QK^T again, dV,
    dP, dK, dQ), each 2 * seq^2/2 * head_dim operations a head; bytes are
    each operand read and each result written once."""
    mm = 2.0 * (seq * seq / 2.0) * head_dim * heads * batch
    q = batch * heads * seq * head_dim * itemsize
    kv = batch * kv_heads * seq * head_dim * itemsize
    lse = batch * heads * seq * 4
    fwd_bytes = q + 2 * kv + q + lse                 # q,k,v -> o,lse
    # q,k,v,o,do,lse in; dq,dk,dv out
    bwd_bytes = (q + 2 * kv + q + q + lse) + (q + 2 * kv)
    return {"fwd_flops": 2 * mm, "bwd_flops": 5 * mm,
            "fwd_bytes": float(fwd_bytes), "bwd_bytes": float(bwd_bytes)}


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """The least time the chip could take and which peak bounds it."""
    t_ops = flops / peaks["bf16_flops"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_mem else (t_mem, "bytes")
