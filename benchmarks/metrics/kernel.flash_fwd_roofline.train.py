"""Roofline share of the flash forward kernel over the traced steps: the
device events called `flash_fwd` (ops/attention.py, KERNEL_FWD), their
count times one call's least time (the larger of required operations over
197 TFLOP/s and bytes over 819 GB/s) over their device time. Counting the
events themselves keeps it right under any remat policy: a recomputed
forward is one more call."""
from benchmarks.harness.spans import kernel_roofline


def read(run):
    return kernel_roofline(run, ["flash_fwd"], "fwd")
