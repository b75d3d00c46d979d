"""Roofline share of the flash forward kernel over the traced steps: the
device events called `flash_fwd` (ops/attention.py, KERNEL_FWD), their
count times one call's least time (the larger of required operations over
197 TFLOP/s and bytes over 819 GB/s) over their device time. Counting the
events themselves keeps it right under any remat policy: a recomputed
forward is one more call. Reads the model's head counts, so it is listed
for cells whose model has them."""
from benchmarks.harness.required_ops import flash_call, roofline_share
from benchmarks.harness.spans import kernel_calls


def read(run):
    found = kernel_calls(run, ["flash_fwd"])
    if found is None:
        return None
    calls, spent = found
    z, s = run["sizes"], run["samples"]
    one = flash_call(s["batch"], z.heads, z.kv_heads, s["seq_len"],
                     z.head_dim)
    return roofline_share(calls * one["fwd_flops"], calls * one["fwd_bytes"],
                          spent, run["peaks"])
