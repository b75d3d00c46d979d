"""Roofline share of a prefill's indexer and selected attention over the
traced prefills: what the algorithm needs for the true tokens of each (the
model module's `dsa_prefill_call`: the causal half of `tokens^2 x 32 x 128 x
2` operations for the index scores, and each query's min(position + 1, 2048)
chosen keys at 64 heads of 256 for QK^T and 256 for PV; operations bound it)
over the device time of `r.attn_index` and `r.attn_core` in the executions of
the prefill programs (`jit__pre`). The tokens are the `tokens` of the traced
`engine.prefill` spans, scaled by the executions counted over the spans
counted. The padding of a prompt to its bucket, the keys a flash forward
multiplies and then masks, and the passes that find each query's threshold
are the program's cost, which lowers this share."""
from benchmarks.harness.dsa_events import (CORE, INDEX, PREFILL_PROGRAM,
                                           region_seconds)
from benchmarks.harness.required_ops import roofline_share
from benchmarks.harness.spans import PREFILL, of_run


def read(run):
    need_of = getattr(run["model"], "dsa_prefill_call", None)
    found, r = region_seconds(run, PREFILL_PROGRAM, (INDEX, CORE)), \
        of_run(run)
    if need_of is None or found is None or r is None:
        return None
    prefills = [s for s in r.named(PREFILL) if "tokens" in s.stats]
    if not prefills:
        return None
    flops = nbytes = 0.0
    for s in prefills:
        need = need_of(run["sizes"], int(s.stats["tokens"]))
        flops, nbytes = flops + need["flops"], nbytes + need["bytes"]
    share = found[0] / float(len(prefills))
    return roofline_share(share * flops, share * nbytes, found[1],
                          run["peaks"])
