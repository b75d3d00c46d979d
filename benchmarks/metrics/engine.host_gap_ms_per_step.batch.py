"""Idle milliseconds of the device per decode step that the host stood in
the way: every idle gap of the traced span that is not under
`engine.wait_for_work` (no request at all), over the number of
`engine.decode_dispatch` spans. From the program's own spans
(harness/spans.py)."""
from benchmarks.harness.spans import per_decode_step_ms


def read(run):
    return per_decode_step_ms(run)
