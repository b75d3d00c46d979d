"""How late the load generator sent: sent minus due, 99th percentile. A
guard on the generator, not a target: a starved generator reads as a fast
server."""
from benchmarks.harness.stats import percentile


def read(run):
    p = percentile(run["samples"].get("lag_s", []), 99)
    return None if p is None else p * 1e3
