"""Device milliseconds a training step spends in the head: the final norm, the
vocabulary matmul and the loss, forward, backward and recomputation together:
`r.head` (`ray_tpu/models/regions.py`) of one execution of the step
(`jit__step`), median over the traced steps (`harness/op_scopes.py`). None for
a program without regions."""
from benchmarks.harness.op_scopes import region_ms

REGIONS = ("r.head",)


def read(run):
    return region_ms(run, "jit__step", REGIONS)
