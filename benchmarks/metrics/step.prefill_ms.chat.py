"""Device time of the prefill programs (`_pre` in serve/llm/engine.py, one
per padded length, all jitted as `jit__pre`) per admitted request: their
summed device time over their executions in the trace."""
from benchmarks.harness.xplane import program_times

PROGRAM = "jit__pre"


def read(run):
    if run["trace"] is None:
        return None
    times = program_times(run["trace"]).get(PROGRAM)
    return sum(times) / len(times) * 1e3 if times else None
