"""Device milliseconds a decode step spends in the attention over the rows
its indexer chose: `r.attn_core` of one execution of the decode program
(`jit__step`), all layers, median over the traced executions, in a cell whose
model has an indexer (`region.attn_core_ms.batch` is the same reading for
every cell). None for a program that writes no `dsa_*` count."""
from benchmarks.harness.dsa_events import CORE, emit_counts, step_region_ms


def read(run):
    if emit_counts(run) is None:
        return None
    return step_region_ms(run, CORE)
