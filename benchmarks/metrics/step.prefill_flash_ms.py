"""Device milliseconds a prefill spends in its flash forward: the device
time of the events called `flash_fwd` (ops/attention.py, one an attention
and prefill) over the traced `engine.prefill` spans. The blocks the program
gives the kernel move this (a grid step costs what a small block's matmuls
do) where the roofline's share of the same events also moves with the
prompts' lengths; beside `step.prefill_ms.*` it says what part of a prefill
the flash forward is. A prefill dispatched at the trace's edge may have its
span on one side and its kernels on the other: one prefill in some tens.
None where the trace holds no such event or span. Listed for the cells whose
prefill runs the unwindowed forward of a latent attention."""
from benchmarks.harness.spans import PREFILL, kernel_calls, of_run


def read(run):
    found, r = kernel_calls(run, ["flash_fwd"]), of_run(run)
    if found is None or r is None:
        return None
    prefills = r.named(PREFILL)
    return 1e3 * found[1] / len(prefills) if prefills else None
