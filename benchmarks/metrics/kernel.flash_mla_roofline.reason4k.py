"""Roofline share of the flash forward over the traced prefills of a model
whose keys are wider than its values: what the algorithm needs for the true
tokens of each prefill at the true widths (the model module's
`flash_prefill_call`: QK^T at 192 and PV at 128 numbers a head, all 8
attentions; bytes bound it under some 900 tokens and operations above)
over the device time of the events called `flash_fwd` (ops/attention.py,
one an attention and prefill). The tokens are the `tokens` of the traced
`engine.prefill` spans; a prefill dispatched at the trace's edge may have
its span on one side and its kernels on the other, so what the spans
require is scaled by the kernels counted over the kernels the spans would
give. The padding of a prompt to its bucket and the keys a query block
reads again are the program's cost, which lowers this share. Listed for
cells whose model module has `flash_prefill_call`."""
from benchmarks.harness.required_ops import roofline_share
from benchmarks.harness.spans import PREFILL, kernel_calls, of_run


def read(run):
    need_of = getattr(run["model"], "flash_prefill_call", None)
    found, r = kernel_calls(run, ["flash_fwd"]), of_run(run)
    if need_of is None or found is None or r is None:
        return None
    prefills = [s for s in r.named(PREFILL) if "tokens" in s.stats]
    if not prefills:
        return None
    calls, spent = found
    flops = nbytes = 0.0
    for s in prefills:
        need = need_of(run["sizes"], int(s.stats["tokens"]))
        flops, nbytes = flops + need["flops"], nbytes + need["bytes"]
    share = calls / float(run["sizes"].attentions * len(prefills))
    return roofline_share(share * flops, share * nbytes, spent, run["peaks"])
