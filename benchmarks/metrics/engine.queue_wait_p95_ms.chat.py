"""Wait from submit to first admission, 95th percentile, from the engine's
own `EngineCore._queue_waits` over the window."""
from benchmarks.harness.stats import percentile


def read(run):
    p = percentile(run["samples"].get("queue_wait_s", []), 95)
    return None if p is None else p * 1e3
