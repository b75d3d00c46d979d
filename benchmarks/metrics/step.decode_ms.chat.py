"""Device time of one execution of the decode program (`_step` in
serve/llm/engine.py, jitted as `jit__step`), median over the trace."""
import statistics

from benchmarks.harness.xplane import program_times

PROGRAM = "jit__step"


def read(run):
    if run["trace"] is None:
        return None
    times = program_times(run["trace"]).get(PROGRAM)
    return statistics.median(times) * 1e3 if times else None
