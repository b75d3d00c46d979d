"""Roofline share of the KDA layers' decode recurrence over the traced
steps: what the kernel has to move for the lanes whose state the steps read
and wrote (the model module's `kda_step_call`: a layer's float32 state in
and out, q, k, v, the decays a head and key channel and the betas in, the
outputs out; bytes bound it at 819 GB/s) over the device time of the events
called `kda_step` (ops/kda.py, one a KDA layer and step). The lane-steps
are the sum of `engine.decode_dispatch`'s `state_slots` over the traced
spans; a step dispatched at the trace's edge may have its span on one side
and its kernels on the other, so what the spans require is scaled by the
kernels counted over the kernels the spans would give. None for a program
that writes no `state_slots` or holds no such kernel (the parent of PR 50).
Listed for cells whose model module has `kda_step_call`."""
from benchmarks.harness.required_ops import roofline_share
from benchmarks.harness.spans import DISPATCH, kernel_calls, of_run

KEY = "state_slots"


def read(run):
    need_of = getattr(run["model"], "kda_step_call", None)
    found, r = kernel_calls(run, ["kda_step"]), of_run(run)
    if need_of is None or found is None or r is None:
        return None
    steps = [s for s in r.named(DISPATCH) if KEY in s.stats]
    linear = len(run["sizes"].of_kind("linear_attention"))
    if not steps or not linear:
        return None
    calls, spent = found
    need = need_of(run["sizes"], sum(int(s.stats[KEY]) for s in steps))
    share = calls / float(linear * len(steps))
    return roofline_share(share * need["flops"], share * need["bytes"],
                          spent, run["peaks"])
