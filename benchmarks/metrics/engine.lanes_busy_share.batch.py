"""Decode lanes holding a sequence, mean over the window's engine steps,
as a share of `max_batch`."""


def read(run):
    lanes = run["samples"].get("lanes")
    if not lanes:
        return None
    return 100.0 * sum(lanes) / (len(lanes) * run["samples"]["max_batch"])
