"""Time to first token, 90th percentile (nearest rank; 396 requests at the
chat cell's 8.4 requests/s, 39 beyond it). Recorded, not judged: a stalled
step (PERF.md, Findings 4) moves it by its own length."""
from benchmarks.harness.stats import percentile


def read(run):
    p = percentile(run["samples"].get("ttft_s", []), 90)
    return None if p is None else p * 1e3
