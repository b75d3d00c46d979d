"""Roofline share of the flash backward over the traced steps: one
backward is one `flash_bwd_dkdv` and one `flash_bwd_dq` event
(ops/attention.py); the count of the first times one backward's least time
over the device time of both. Reads the model's head counts, so it is
listed for cells whose model has them."""
from benchmarks.harness.required_ops import flash_call, roofline_share
from benchmarks.harness.spans import kernel_calls


def read(run):
    found = kernel_calls(run, ["flash_bwd_dkdv", "flash_bwd_dq"])
    if found is None:
        return None
    calls, spent = found
    z, s = run["sizes"], run["samples"]
    one = flash_call(s["batch"], z.heads, z.kv_heads, s["seq_len"],
                     z.head_dim)
    return roofline_share(calls * one["bwd_flops"], calls * one["bwd_bytes"],
                          spent, run["peaks"])
