"""Percent of the decode program's (`jit__step`) operation time under no region
of `ray_tpu/models/regions.py`: how far the `region.*` metrics of the same line
can be trusted. What is left there is XLA's own (asynchronous copies between
memories, a loop's slices of stacked weights, fusions whose metadata the
compiler dropped); `tools/regions.py` lists the largest with their source
lines. None for a program without regions."""
from benchmarks.harness.op_scopes import unscoped_share


def read(run):
    return unscoped_share(run, "jit__step")
