"""Device milliseconds a decode step spends in attention's way in: the q / k /
v or latent projections, the absorbed form's key relay, rotary, the cache
write: `r.attn_in` (`ray_tpu/models/regions.py`) of one execution of the decode
program (`jit__step`), median over the traced executions; operations filed by
the `r.*` scope of their `tf_op` path (`harness/op_scopes.py`). None for a
program without regions."""
from benchmarks.harness.op_scopes import region_ms

REGIONS = ("r.attn_in",)


def read(run):
    return region_ms(run, "jit__step", REGIONS)
