"""Decode lanes holding a sequence as a share of `max_batch`, over the
traced decode steps: the `lanes` attribute the engine writes on each
`engine.decode_dispatch` span (its counter `decode_lane_steps` over
`decode_steps`). The inside twin of engine.lanes_busy_share.batch, which
the benchmark's wrapper around `step` counts."""
from benchmarks.harness.spans import DISPATCH, of_run


def read(run):
    r = of_run(run)
    steps = len(r.named(DISPATCH)) if r else 0
    if not steps:
        return None
    return 100.0 * r.attr_sum(DISPATCH, "lanes") / (
        steps * run["cfg"]["deployment"]["max_batch"])
