"""Output tokens that reached their clients inside the window, over the
window's length. Host clock."""
from benchmarks.harness.stats import rate_per_s


def read(run):
    if "gap_s" not in run["samples"]:
        return None
    return rate_per_s(run["samples"]["tokens_in_window"], run["seconds"])
