"""Device milliseconds a training step (`jit__step`) spends in the backward
pass (operations under `transpose(`), by the operations' `tf_op` path
(`harness/op_scopes.py: pass_of`), median over the traced steps. None for a
program without regions."""
from benchmarks.harness.op_scopes import pass_ms


def read(run):
    return pass_ms(run, "jit__step", "backward")
