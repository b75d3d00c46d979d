"""A traced serving run's decode steps seen from both sides, for the
metrics of a model with routed experts and a latent cache: the device
events inside each execution of the decode program (`jit__step`), and the
counts the program wrote on the spans of the same steps
(`engine.decode_dispatch`: lanes and live positions; `engine.emit`: the
step's `moe_pairs`, `moe_experts_touched`, `moe_load_max`, which the model
counted on the device and the engine fetched with the step's tokens).
A program that writes no such span or kernel (the parent of PR 29, a dense
model) gives None everywhere, and the metric leaves its line.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional

from benchmarks.harness import spans, xplane

PROGRAM = "jit__step"
EMIT = "engine.emit"
KERNEL_MLA = "mla_paged_decode_attn"
KERNEL_GMM = "moe_gmm"


def steps(run: dict) -> Optional[List[xplane.Event]]:
    """The traced executions of the decode program on device 0."""
    trace = run.get("trace")
    if trace is None or not trace.modules:
        return None
    evs = [e for e in trace.modules[min(trace.modules)]
           if xplane.program_name(e.name) == PROGRAM]
    return evs or None


def kernels_by_step(run: dict, name: str
                    ) -> Optional[List[List[xplane.Event]]]:
    """For each traced decode step, the device events of the kernel called
    `name` inside it, in time order; None where there is no step or no such
    event at all. Events of the same kernel in another program (a prefill
    runs the grouped matmul too) fall in no step and are left out."""
    progs = steps(run)
    if progs is None:
        return None
    events = xplane.kernel_events(run["trace"],
                                  rf"^%{re.escape(name)}[.\d]* = ")
    if not events:
        return None
    starts = [p.start for p in progs]
    out: List[List[xplane.Event]] = [[] for _ in progs]
    for e in events:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= progs[i].end + 1e-9:
            out[i].append(e)
    return out


def emit_counts(run: dict) -> Optional[Dict[str, int]]:
    """The model's counts summed over the traced decode steps, with `steps`
    the number of steps that carried them; None where no span has them."""
    r = spans.of_run(run)
    if r is None:
        return None
    emits = [s for s in r.named(EMIT) if "moe_pairs" in s.stats]
    if not emits:
        return None
    out = {"steps": len(emits)}
    for key in ("moe_pairs", "moe_experts_touched", "moe_load_max"):
        out[key] = sum(int(s.stats.get(key, 0)) for s in emits)
    return out
