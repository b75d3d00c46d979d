"""Device milliseconds a decode step spends in its full layers' attention:
the events called `paged_decode_attn` inside one execution of `jit__step`
(one a full layer), summed a step, median over the traced steps. None for a
program whose step holds no such kernel; listed for the cell whose model
has layers of both kinds, where `step.attn_window_ms.mixed8k` is its
twin."""
import statistics

from benchmarks.harness.decode_events import kernels_by_step


def read(run):
    by_step = kernels_by_step(run, "paged_decode_attn")
    if by_step is None:
        return None
    per_step = [sum(e.dur for e in evs) for evs in by_step if evs]
    return 1e3 * statistics.median(per_step) if per_step else None
