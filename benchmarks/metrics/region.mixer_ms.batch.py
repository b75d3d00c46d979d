"""Device milliseconds a decode step spends in its recurrent mixers:
projections and convolution step, the recurrence and the state's write, the
gated norm and output projection: `r.mixer_in`, `r.mixer_core`, `r.mixer_out`
(`ray_tpu/models/regions.py`) of one execution of the decode program
(`jit__step`), median over the traced executions; operations filed by the `r.*`
scope of their `tf_op` path (`harness/op_scopes.py`). None for a program
without regions."""
from benchmarks.harness.op_scopes import region_ms

REGIONS = ("r.mixer_in", "r.mixer_core", "r.mixer_out")


def read(run):
    return region_ms(run, "jit__step", REGIONS)
