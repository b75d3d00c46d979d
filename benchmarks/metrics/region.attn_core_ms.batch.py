"""Device milliseconds a decode step spends in the attention itself: the paged
/ window / latent decode kernel, or the gather and einsum that stand in for it:
`r.attn_core` (`ray_tpu/models/regions.py`) of one execution of the decode
program (`jit__step`), median over the traced executions; operations filed by
the `r.*` scope of their `tf_op` path (`harness/op_scopes.py`). None for a
program without regions."""
from benchmarks.harness.op_scopes import region_ms

REGIONS = ("r.attn_core",)


def read(run):
    return region_ms(run, "jit__step", REGIONS)
