"""Device milliseconds a decode step spends in its latent attentions: the
events called `mla_paged_decode_attn` inside one execution of `jit__step`
(two a double layer: 8 at 4 layers), summed a step, median over the traced
steps. None for a program whose step holds no such kernel. Here the kernel
has 64 query rows a lane (109 operations a byte of cache row) where the
other latent cell has 20."""
import statistics

from benchmarks.harness.decode_events import KERNEL_MLA, kernels_by_step


def read(run):
    by_step = kernels_by_step(run, KERNEL_MLA)
    if by_step is None:
        return None
    per_step = [sum(e.dur for e in evs) for evs in by_step if evs]
    return 1e3 * statistics.median(per_step) if per_step else None
