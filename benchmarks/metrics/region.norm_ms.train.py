"""Device milliseconds a training step spends in its layers' RMS norms,
forward, backward and recomputation together: `r.norm`
(`ray_tpu/models/regions.py`) of one execution of the step (`jit__step`),
median over the traced steps (`harness/op_scopes.py`). None for a program
without regions."""
from benchmarks.harness.op_scopes import region_ms

REGIONS = ("r.norm",)


def read(run):
    return region_ms(run, "jit__step", REGIONS)
