"""Operations and bytes the kernels' algorithms require, from shapes alone,
and the least time a chip could take for them. What the program recomputes
(remat, the two-kernel backward's second QK^T and dP) does not count. A
whole model's counts (`matmul_params`, `train_flops_per_token`) are its
module's, under `benchmarks/models/`."""
from __future__ import annotations


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


def paged_decode_call(live_positions: int, lanes: int, layers: int,
                      kv_dim: int, q_dim: int, itemsize: int = 2) -> dict:
    """Decode attention over a paged cache, all layers, as the algorithm
    needs it, for one step or (the counts being sums) for many: every live
    position's key and value (`kv_dim` numbers each) read once a layer, each
    lane's query in and output out; QK^T and PV are 2 * live * q_dim
    operations each a layer. A page's unused tail, which a kernel that
    copies whole pages reads too, does not count."""
    kv = 2 * live_positions * kv_dim * itemsize
    q_and_o = 2 * lanes * q_dim * itemsize
    return {"flops": 2.0 * 2.0 * live_positions * q_dim * layers,
            "bytes": float(layers * (kv + q_and_o))}


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """The least time the chip could take and which peak bounds it."""
    t_ops = flops / peaks["bf16_flops"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_mem else (t_mem, "bytes")


def roofline_share(flops: float, nbytes: float, spent_s: float,
                   peaks: dict) -> float:
    """Per cent of its roofline: the least time over the time spent."""
    return 100.0 * roofline_seconds(flops, nbytes, peaks)[0] / spent_s
