"""Gap between a request's consecutive tokens at the client, 99th percentile
of all gaps that ended inside the window. Host clock. The rank has to lie
well inside one cluster of gaps, because a run on a busier host has some
tens more long gaps than a quiet one and the rank moves by as many:

- at the chat cell's 8.4 requests/s (PR 32: 396 requests, some 32,400 gaps,
  324 beyond the rank) it lies in the cluster of a decode step plus a
  1024-bucket prefill (some 310 gaps at 34-36 ms), 130 gaps from its lower
  edge and 180 from its upper: 50 gaps either way move it by 0.25 ms;
- at 4.4 requests/s after PR 30 (14,800 gaps, 148 beyond) it lay 32 gaps
  under the upper edge of the 512-bucket cluster (18.7-20.4 ms, 97 gaps)
  with the 1024-bucket cluster (33-35 ms) next: 20 gaps more read +1.5 %,
  33 more +68 %, which is what the check of PR 32 saw;
- at 1.74 requests/s after PR 27 it lay two gaps from such an edge."""
from benchmarks.harness.stats import percentile


def read(run):
    p = percentile(run["samples"].get("gap_s", []), 99)
    return None if p is None else p * 1e3
