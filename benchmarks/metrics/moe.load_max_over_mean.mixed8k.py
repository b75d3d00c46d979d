"""The busiest expert's pairs over the mean expert's, an expert layer and
decode step, weighed by pairs over the traced steps: the engine's counter
`moe_load_max` (the largest number of pairs any expert of a layer got,
summed over layers and steps) over `moe_pairs / num_experts`. 1 would be an
even routing; with 256 pairs over 256 experts the mean is 1 and the busiest
expert's rows are what one group of the grouped matmul has to hold."""
from benchmarks.harness.decode_events import emit_counts


def read(run):
    counts, cfg = emit_counts(run), run["cfg"]
    if counts is None or not counts["moe_pairs"] \
            or "num_experts" not in cfg:
        return None
    return counts["moe_load_max"] * cfg["num_experts"] / counts["moe_pairs"]
