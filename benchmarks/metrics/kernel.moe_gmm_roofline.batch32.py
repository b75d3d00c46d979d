"""Roofline share of the routed experts' grouped matmul over the traced
decode steps: what the algorithm has to move (the model module's
`moe_gmm_call`: the three matrices of every expert that got a pair read
once, each pair's activation in and result out, 6 x 2048 x 1536 operations
a pair; bytes bound it at 128 pairs a layer) over the device time of the
events called `moe_gmm` (ops/grouped_matmul.py, three a layer) inside the
executions of `jit__step`. Pairs and experts touched are what the model
counted on the device in those very steps, read from the `engine.emit`
spans of the same traced span; experts that got no pair are not counted as
required, so reading them would lower this share and cannot raise it. The
prefills' grouped matmuls are left out on both sides."""
from benchmarks.harness.decode_events import (KERNEL_GMM, emit_counts,
                                              kernels_by_step)
from benchmarks.harness.required_ops import roofline_share


def read(run):
    need_of = getattr(run["model"], "moe_gmm_call", None)
    by_step, counts = kernels_by_step(run, KERNEL_GMM), emit_counts(run)
    if need_of is None or by_step is None or counts is None:
        return None
    spent = sum(e.dur for evs in by_step for e in evs)
    if not spent or not counts["moe_pairs"]:
        return None
    need = need_of(run["sizes"], counts["moe_pairs"],
                   counts["moe_experts_touched"])
    return roofline_share(need["flops"], need["bytes"], spent, run["peaks"])
