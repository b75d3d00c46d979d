"""Time to first token, 90th percentile (nearest rank; 96 requests, 9 beyond
it). Recorded, not judged: at 0.6 of the knee this rank sits on the edge of
the lane wait, where neighbouring requests read 250, 340 and 520 ms."""
from benchmarks.harness.stats import percentile


def read(run):
    p = percentile(run["samples"].get("ttft_s", []), 90)
    return None if p is None else p * 1e3
