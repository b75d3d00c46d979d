"""Roofline share of the state-space layers' decode recurrence over the
traced steps: what the kernel has to move for the lanes whose state the
steps read and wrote (the model module's `ssd_step_call`: a layer's float32
state in and out, x, B, C and the steps in, the outputs out; bytes bound it
at 819 GB/s) over the device time of the events called `ssd_step`
(ops/ssd.py, one a state-space layer and step). The lane-steps are the sum
of `engine.decode_dispatch`'s `state_slots` over the traced spans; a step
dispatched at the trace's edge may have its span on one side and its
kernels on the other, so what the spans require is scaled by the kernels
counted over the kernels the spans would give
(`kernel.delta_step_roofline.answers3k` does the same for its kernel). None
for a program that writes no `state_slots` or holds no such kernel. Listed
for cells whose model module has `ssd_step_call`."""
from benchmarks.harness.required_ops import roofline_share
from benchmarks.harness.spans import DISPATCH, kernel_calls, of_run

KEY = "state_slots"


def read(run):
    need_of = getattr(run["model"], "ssd_step_call", None)
    found, r = kernel_calls(run, ["ssd_step"]), of_run(run)
    if need_of is None or found is None or r is None:
        return None
    steps = [s for s in r.named(DISPATCH) if KEY in s.stats]
    layers = len(run["sizes"].of_kind("M"))
    if not steps or not layers:
        return None
    calls, spent = found
    need = need_of(run["sizes"], sum(int(s.stats[KEY]) for s in steps))
    share = calls / float(layers * len(steps))
    return roofline_share(share * need["flops"], share * need["bytes"],
                          spent, run["peaks"])
