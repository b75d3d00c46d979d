"""Device milliseconds a decode step spends in its layers' RMS norms (the final
norm is the head's): `r.norm` (`ray_tpu/models/regions.py`) of one execution of
the decode program (`jit__step`), median over the traced executions; operations
filed by the `r.*` scope of their `tf_op` path (`harness/op_scopes.py`). None
for a program without regions."""
from benchmarks.harness.op_scopes import region_ms

REGIONS = ("r.norm",)


def read(run):
    return region_ms(run, "jit__step", REGIONS)
