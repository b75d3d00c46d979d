"""Gap between a request's consecutive tokens at the client, 99th percentile
of all gaps that ended inside the window (some 6400 gaps, 64 beyond it).
Host clock. Not the 98th: at this cell's rate that rank lies on the boundary
between two clusters of gaps (a decode step plus a 512- or a 1024-bucket
prefill, 62 and 76 ms) and flips between them from run to run."""
from benchmarks.harness.stats import percentile


def read(run):
    p = percentile(run["samples"].get("gap_s", []), 99)
    return None if p is None else p * 1e3
