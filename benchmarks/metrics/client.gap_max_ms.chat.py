"""The longest gap between a request's consecutive tokens that ended inside
the window. A quiet run reads 140-160 ms (a decode step with a 2048-bucket
prefill inside it); a run in which one engine step took seconds reads those
seconds, so this is where such a step shows (PERF.md, Findings 4)."""


def read(run):
    gaps = run["samples"].get("gap_s")
    return max(gaps) * 1e3 if gaps else None
