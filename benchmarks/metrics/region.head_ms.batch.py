"""Device milliseconds a decode step spends at its end: the final norm and the
vocabulary matmul (`r.head` of `jit__step`) and the choice of each lane's next
token (`r.sample` of `jit__next`, which runs once a step), each the median over
the traced executions (`harness/op_scopes.py`, `ray_tpu/models/regions.py`).
None for a program without regions."""
from benchmarks.harness.op_scopes import region_ms


def read(run):
    head = region_ms(run, "jit__step", ("r.head",))
    if head is None:
        return None
    return head + (region_ms(run, "jit__next", ("r.sample",)) or 0.0)
