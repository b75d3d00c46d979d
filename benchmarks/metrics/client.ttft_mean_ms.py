"""Time to first token, mean over every request due in the window, from the
instant each was due to its first token's arrival at the client (a request is
followed past the window's end to its first token). Host clock. Recorded, not
judged: one engine step that takes S seconds (PERF.md, Findings 4) adds
S^2 / (2 x 51 x 0.4) to it, 25 ms of 156 for one second, so a set of runs in
which two have such a step cannot stand under any bound the check allows."""


def read(run):
    ttft = run["samples"].get("ttft_s")
    return sum(ttft) / len(ttft) * 1e3 if ttft else None
