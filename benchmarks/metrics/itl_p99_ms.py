"""Gap between a request's consecutive tokens at the client, 99th percentile
of all gaps that ended inside the window (some 14,600 gaps at the chat
cell's 4.4 requests/s, 146 beyond it). Host clock. At that rate the rank
lies some 25 gaps inside the cluster of a decode step plus a 1024-bucket
prefill (40-42 ms, which reaches past the 99.5th); at 1.74 requests/s,
after PR 27 had made the step four times shorter, it lay two gaps from the
edge between two clusters (21.9 and 24.8 ms) and flipped from run to run."""
from benchmarks.harness.stats import percentile


def read(run):
    p = percentile(run["samples"].get("gap_s", []), 99)
    return None if p is None else p * 1e3
