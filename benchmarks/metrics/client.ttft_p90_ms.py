"""Time to first token, 90th percentile (nearest rank; 219 requests at the
chat cell's 4.4 requests/s, 21 beyond it). Recorded, not judged: a stalled
step (PERF.md, Findings 4) moves it by its own length."""
from benchmarks.harness.stats import percentile


def read(run):
    p = percentile(run["samples"].get("ttft_s", []), 90)
    return None if p is None else p * 1e3
