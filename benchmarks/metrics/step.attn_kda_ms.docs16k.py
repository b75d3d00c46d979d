"""Device milliseconds a decode step spends in its KDA layers' recurrence:
the events called `kda_step` (ops/kda.py) inside one execution of
`jit__step` (one a KDA layer: six at seven layers), summed a step, median
over the traced steps. None for a program whose step holds no such kernel
(every model but this kind; the parent of PR 50); listed for the cell whose
model has KDA layers beside a latent one, where
`step.attn_latent_ms.reason4k` reads the latent layer's share."""
import statistics

from benchmarks.harness.decode_events import kernels_by_step


def read(run):
    by_step = kernels_by_step(run, "kda_step")
    if by_step is None:
        return None
    per_step = [sum(e.dur for e in evs) for evs in by_step if evs]
    return 1e3 * statistics.median(per_step) if per_step else None
