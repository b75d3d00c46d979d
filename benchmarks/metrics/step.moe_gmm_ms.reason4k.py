"""Device milliseconds a decode step spends in the held experts' grouped
matmuls: the events called `moe_gmm` inside one execution of `jit__step`
(three a double layer: 12 at 4 layers), summed a step, median over the
traced steps. The expert branch of this model is not in line (it reads the
first half's normed stream and joins at the layer's end), so the span
between attention kernels that `step.moe_ms.batch32` measures has no
meaning here; the routing, the sort and the combine around the kernels are
not in this number."""
import statistics

from benchmarks.harness.decode_events import KERNEL_GMM, kernels_by_step


def read(run):
    by_step = kernels_by_step(run, KERNEL_GMM)
    if by_step is None:
        return None
    per_step = [sum(e.dur for e in evs) for evs in by_step if evs]
    return 1e3 * statistics.median(per_step) if per_step else None
