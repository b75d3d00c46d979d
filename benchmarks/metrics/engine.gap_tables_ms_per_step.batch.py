"""The part of the host's gap per decode step under `engine.page_tables`
and `engine.decode_dispatch`: building the step's four host arrays, their
`device_put`s and the dispatch itself."""
from benchmarks.harness.spans import DISPATCH, TABLES, per_decode_step_ms


def read(run):
    return per_decode_step_ms(run, TABLES, DISPATCH)
