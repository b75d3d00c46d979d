"""Tokens through forward, backward and AdamW in the window, over its
length (closed by block_until_ready) and the cell's chips. Host clock."""
from benchmarks.harness.stats import rate_per_s


def read(run):
    if "losses" not in run["samples"]:
        return None
    return rate_per_s(run["samples"]["tokens_in_window"],
                      run["seconds"]) / int(run["cell"]["chips"])
