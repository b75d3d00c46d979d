"""Device milliseconds a decode step spends in its state-space layers'
recurrence: the events called `ssd_step` (ops/ssd.py) inside one execution
of `jit__step` (one a state-space layer), summed a step, median over the
traced steps. The projections, the convolution's step (a gather and a
scatter of the tail beside the kernel), the gate and the norm around the
kernel are not in this number. None for a program whose step holds no such
kernel."""
import statistics

from benchmarks.harness.decode_events import kernels_by_step


def read(run):
    by_step = kernels_by_step(run, "ssd_step")
    if by_step is None:
        return None
    per_step = [sum(e.dur for e in evs) for evs in by_step if evs]
    return 1e3 * statistics.median(per_step) if per_step else None
