"""Roofline share of the flash backward over the traced steps: one
backward is one `flash_bwd_dkdv` and one `flash_bwd_dq` event
(ops/attention.py); the count of the first times one backward's least time
over the device time of both."""
from benchmarks.harness.spans import kernel_roofline


def read(run):
    return kernel_roofline(run, ["flash_bwd_dkdv", "flash_bwd_dq"], "bwd")
