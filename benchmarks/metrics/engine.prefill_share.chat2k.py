"""What this traffic's admissions cost the device: device seconds of the
prefill programs (`_pre` in serve/llm/engine.py, one per padded length, all
jitted as `jit__pre`) inside the traced window over the device's busy
seconds there (`xplane.traced_window`), in per cent. Each admitted request
is a prefill of one sequence that reads every weight; the decode steps of
all lanes wait behind it in the one queue. None for an untraced run or a
trace without such a program."""
from benchmarks.harness import xplane

PROGRAM = "jit__pre"


def read(run):
    trace = run["trace"]
    if trace is None or not trace.modules:
        return None
    w = xplane.traced_window(trace)
    shift = xplane.clock_shift_s(trace)
    spent = sum(
        max(0.0, min(e.end + shift, w.hi) - max(e.start + shift, w.lo))
        for e in trace.modules[min(trace.modules)]
        if xplane.program_name(e.name) == PROGRAM)
    return 100.0 * spent / w.busy_s if spent and w.busy_s else None
