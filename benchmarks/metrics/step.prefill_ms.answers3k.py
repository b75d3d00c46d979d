"""Device time of the prefill programs (`_pre` in serve/llm/engine.py, one
per padded length, all jitted as `jit__pre`) per admitted request in a
closed-loop cell whose prompts fall in five buckets: their summed device
time over their executions in the trace. A prefill here runs the flash
forward on the full layers and the chunked recurrence on the linear ones."""
from benchmarks.harness.xplane import program_times

PROGRAM = "jit__pre"


def read(run):
    if run["trace"] is None:
        return None
    times = program_times(run["trace"]).get(PROGRAM)
    return sum(times) / len(times) * 1e3 if times else None
