"""Device milliseconds a decode step spends in its sliding layers' latent
attention: the events called `mla_paged_window_decode_attn` inside one
execution of `jit__step` (one a sliding layer), summed a step, median over
the traced steps. A lane's window is 513 positions however long the lane, so
this does not grow with the context as `step.attn_sparse_ms.code16k` does.
None for a program without the kernel (the parent of PR 65)."""
import statistics

from benchmarks.harness.decode_events import kernels_by_step
from benchmarks.harness.ring_events import KERNEL


def read(run):
    by_step = kernels_by_step(run, KERNEL)
    if by_step is None:
        return None
    per_step = [sum(e.dur for e in evs) for evs in by_step if evs]
    return 1e3 * statistics.median(per_step) if per_step else None
