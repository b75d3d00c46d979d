"""Device milliseconds a decode step spends in its linear layers'
recurrence: the events called `gated_delta_step` inside one execution of
`jit__step` (one a linear layer), summed a step, median over the traced
steps. None for a program whose step holds no such kernel; listed for the
cell whose model has linear-attention layers beside full ones, where
`step.attn_full_ms.mixed8k` reads the full layers' share."""
import statistics

from benchmarks.harness.decode_events import kernels_by_step


def read(run):
    by_step = kernels_by_step(run, "gated_delta_step")
    if by_step is None:
        return None
    per_step = [sum(e.dur for e in evs) for evs in by_step if evs]
    return 1e3 * statistics.median(per_step) if per_step else None
