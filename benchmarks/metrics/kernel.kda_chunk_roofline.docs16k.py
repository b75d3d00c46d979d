"""Roofline share of the chunked KDA forward over the traced prefills: what
the chunked algorithm needs for the true tokens of each prefill (the model
module's `kda_chunk_call`: the chunked form's operations at 64 positions a
chunk and the bytes of q, k, v, the float32 log decays a head and key
channel, the betas and the outputs, all KDA layers; the larger of the two
times) over the device time of the events called `kda_chunk_fwd`
(ops/kda.py, one a KDA layer and prefill). The tokens are the `tokens` of
the traced `engine.prefill` spans that carry `scan_chunks`; a prefill
dispatched at the trace's edge may have its span on one side and its
kernels on the other, so what the spans require is scaled by the kernels
counted over the kernels the spans would give. The chunk the prompt ends in
is computed whole, its padding masked, and the kernel multiplies float32
factors in several passes of the MXU: the program's cost, which lowers this
share. None for a program that writes no `scan_chunks` or holds no such
kernel (the parent of PR 50). Listed for cells whose model module has
`kda_chunk_call`."""
from benchmarks.harness.required_ops import roofline_share
from benchmarks.harness.spans import PREFILL, kernel_calls, of_run

KEY = "scan_chunks"


def read(run):
    need_of = getattr(run["model"], "kda_chunk_call", None)
    found, r = kernel_calls(run, ["kda_chunk_fwd"]), of_run(run)
    if need_of is None or found is None or r is None:
        return None
    prefills = [s for s in r.named(PREFILL) if KEY in s.stats]
    linear = len(run["sizes"].of_kind("linear_attention"))
    if not prefills or not linear:
        return None
    calls, spent = found
    flops = nbytes = 0.0
    for s in prefills:
        need = need_of(run["sizes"], int(s.stats["tokens"]))
        flops, nbytes = flops + need["flops"], nbytes + need["bytes"]
    share = calls / float(linear * len(prefills))
    return roofline_share(share * flops, share * nbytes, spent, run["peaks"])
