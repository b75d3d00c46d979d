"""The part of the host's gap per decode step under `engine.fetch_tokens`:
the device is idle while its argmax travels to the host (what sampling on
the device would take out of the step)."""
from benchmarks.harness.spans import FETCH, per_decode_step_ms


def read(run):
    return per_decode_step_ms(run, FETCH)
