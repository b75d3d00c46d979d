"""Device milliseconds a training step spends in attention (projections and
rotary, the flash kernels, the output projection), forward, backward and
recomputation together: `r.attn_in`, `r.attn_core`, `r.attn_out`
(`ray_tpu/models/regions.py`) of one execution of the step (`jit__step`),
median over the traced steps (`harness/op_scopes.py`). None for a program
without regions."""
from benchmarks.harness.op_scopes import region_ms

REGIONS = ("r.attn_in", "r.attn_core", "r.attn_out")


def read(run):
    return region_ms(run, "jit__step", REGIONS)
