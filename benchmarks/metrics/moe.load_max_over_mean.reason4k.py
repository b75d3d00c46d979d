"""The busiest held expert's rows over the mean held expert's, a layer and
decode step, weighed by rows over the traced steps: the engine's counter
`moe_load_max` (the largest number of rows any held expert of a layer got,
summed over layers and steps) over `moe_pairs / held experts`. 1 would be
an even routing; at half a row an expert it reads 3 to 4 of chance alone."""
from benchmarks.harness.expert_share import emit_counts


def read(run):
    counts = emit_counts(run)
    if counts is None or not counts["moe_pairs"] \
            or not hasattr(run["sizes"], "held"):
        return None
    return counts["moe_load_max"] * run["sizes"].held / counts["moe_pairs"]
