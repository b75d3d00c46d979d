"""Host milliseconds per decode step that the step thread spends under
`stream.publish`, the token stream's send: the summed duration of its
spans over the number of `engine.decode_dispatch` spans. Host time,
whether or not the device waited through it:
engine.gap_publish_ms_per_step.batch is the idle part."""
from benchmarks.harness.spans import DISPATCH, PUBLISH, of_run


def read(run):
    r = of_run(run)
    steps = len(r.named(DISPATCH)) if r else 0
    if not steps:
        return None
    return 1e3 * sum(s.dur for s in r.named(PUBLISH)) / steps
