"""Device milliseconds a decode step spends in the indexer: `r.attn_index`
(`ray_tpu/models/regions.py`: the index projections, the index pool's write,
the scores of every position the lanes hold against their index keys, the
choice of the 2,048 the attention reads) of one execution of the decode
program (`jit__step`), all layers, median over the traced executions. It is
the part of a step that grows with the context. None for a program without
that region."""
from benchmarks.harness.dsa_events import INDEX, step_region_ms


def read(run):
    return step_region_ms(run, INDEX)
