"""Device milliseconds a training step (`jit__step`) spends in what the step
adds around the loss and its gradient: the optimiser's update (operations under
no transformation), by the operations' `tf_op` path (`harness/op_scopes.py:
pass_of`), median over the traced steps. None for a program without regions."""
from benchmarks.harness.op_scopes import pass_ms


def read(run):
    return pass_ms(run, "jit__step", "plain")
