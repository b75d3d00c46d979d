"""Roofline share of the flash forward over the traced prefills of a served
grouped-query model: what the algorithm needs for the true tokens of each
prefill at the true widths (the model module's `flash_prefill_call`: causal
QK^T and PV at 64 numbers a head, 32 query heads over 8 kv heads, the four
attention layers; q, k, v in and the output out; operations bound it from
some 250 tokens up) over the device time of the events called `flash_fwd`
(ops/attention.py, one an attention layer and prefill). The tokens are the
`tokens` of the traced `engine.prefill` spans; a prefill dispatched at the
trace's edge may have its span on one side and its kernels on the other, so
what the spans require is scaled by the kernels counted over the kernels
the spans would give. The padding of a prompt to its bucket, the masked
half of a diagonal block and a head that fills half the MXU's width are the
program's cost, which lowers this share. None for a program whose module
has no `flash_prefill_call`, whose `Sizes` names no `full_attention`
layers, or whose trace holds no `flash_fwd` event or prefill span."""
from benchmarks.harness.required_ops import roofline_share
from benchmarks.harness.spans import PREFILL, kernel_calls, of_run

KIND = "full_attention"


def read(run):
    need_of = getattr(run["model"], "flash_prefill_call", None)
    of_kind = getattr(run["sizes"], "of_kind", None)
    found, r = kernel_calls(run, ["flash_fwd"]), of_run(run)
    if need_of is None or of_kind is None or found is None or r is None:
        return None
    prefills = [s for s in r.named(PREFILL) if "tokens" in s.stats]
    layers = len(of_kind(KIND))
    if not prefills or not layers:
        return None
    calls, spent = found
    flops = nbytes = 0.0
    for s in prefills:
        need = need_of(run["sizes"], int(s.stats["tokens"]))
        flops, nbytes = flops + need["flops"], nbytes + need["bytes"]
    share = calls / float(layers * len(prefills))
    return roofline_share(share * flops, share * nbytes, spent, run["peaks"])
