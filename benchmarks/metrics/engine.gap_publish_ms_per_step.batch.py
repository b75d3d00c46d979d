"""The part of the host's gap per decode step (see
engine.host_gap_ms_per_step.batch) under `engine.ingest` and the
`stream.publish` inside it: the token stream's send on the step thread."""
from benchmarks.harness.spans import INGEST, PUBLISH, per_decode_step_ms


def read(run):
    return per_decode_step_ms(run, INGEST, PUBLISH)
