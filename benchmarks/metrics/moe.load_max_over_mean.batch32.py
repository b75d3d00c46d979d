"""The busiest expert's pairs over the mean expert's, an expert layer and
decode step, weighed by pairs over the traced steps: the engine's counter
`moe_load_max` (the largest number of pairs any expert of a layer got,
summed over layers and steps) over `moe_pairs / n_routed_experts`. 1 would
be an even routing; the grouped matmul's tile of rows one group can fill
grows with it."""
from benchmarks.harness.decode_events import emit_counts


def read(run):
    counts, cfg = emit_counts(run), run["cfg"]
    if counts is None or not counts["moe_pairs"] \
            or "n_routed_experts" not in cfg:
        return None
    return counts["moe_load_max"] * cfg["n_routed_experts"] \
        / counts["moe_pairs"]
