"""Device milliseconds a decode step spends in its sliding layers'
attention: the events called `paged_window_decode_attn` inside one
execution of `jit__step` (one a sliding layer), summed a step, median over
the traced steps. A lane's window is 512 positions however long the lane,
so this should not grow with the context as `step.attn_full_ms.mixed8k`
does. None for a program without the kernel (the parent of PR 35)."""
import statistics

from benchmarks.harness.decode_events import kernels_by_step


def read(run):
    by_step = kernels_by_step(run, "paged_window_decode_attn")
    if by_step is None:
        return None
    per_step = [sum(e.dur for e in evs) for evs in by_step if evs]
    return 1e3 * statistics.median(per_step) if per_step else None
