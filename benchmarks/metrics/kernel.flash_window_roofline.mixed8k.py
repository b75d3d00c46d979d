"""Roofline share of the windowed flash forward over the traced prefills:
what the algorithm needs for the true tokens of each prefill (the model
module's `flash_window_call`: QK^T and PV for the keys inside each query's
window of 512, all sliding layers; operations bound it at 197 TFLOP/s) over
the device time of the events called `flash_window_fwd` (ops/attention.py,
one a sliding layer and prefill). The tokens are the `tokens` of the traced
`engine.prefill` spans; a prefill dispatched at the trace's edge may have
its span on one side and its kernels on the other, so what the spans
require is scaled by the kernels counted over the kernels the spans would
give. The padding of a prompt to its bucket and what a block holds outside
the window are computed and masked: the program's cost, which lowers this
share. Listed for cells whose model module has `flash_window_call`."""
from benchmarks.harness.required_ops import roofline_share
from benchmarks.harness.spans import PREFILL, kernel_calls, of_run


def read(run):
    need_of = getattr(run["model"], "flash_window_call", None)
    found, r = kernel_calls(run, ["flash_window_fwd"]), of_run(run)
    if need_of is None or found is None or r is None:
        return None
    prefills = [s for s in r.named(PREFILL) if "tokens" in s.stats]
    sliding = len(run["sizes"].of_kind("sliding_attention"))
    if not prefills or not sliding:
        return None
    calls, spent = found
    flops = nbytes = 0.0
    for s in prefills:
        need = need_of(run["sizes"], int(s.stats["tokens"]))
        flops, nbytes = flops + need["flops"], nbytes + need["bytes"]
    share = calls / float(sliding * len(prefills))
    return roofline_share(share * flops, share * nbytes, spent, run["peaks"])
