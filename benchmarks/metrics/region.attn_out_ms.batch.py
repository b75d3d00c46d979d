"""Device milliseconds a decode step spends in attention's way out: the
absorbed form's value relay, a head gate, the output projection and its
residual addition: `r.attn_out` (`ray_tpu/models/regions.py`) of one execution
of the decode program (`jit__step`), median over the traced executions;
operations filed by the `r.*` scope of their `tf_op` path
(`harness/op_scopes.py`). None for a program without regions."""
from benchmarks.harness.op_scopes import region_ms

REGIONS = ("r.attn_out",)


def read(run):
    return region_ms(run, "jit__step", REGIONS)
