"""Rows a held expert gets an expert layer and decode step, over the traced
steps: the engine's counter `moe_pairs` over `held experts x expert layers x
steps`, the expert layers counted from the model module's own `Sizes`
(`moe.pairs_per_held_expert.reason4k` divides by every layer; here five of
eleven hold experts). It says how near the cell is to the deployment's
expert load: 32 lanes x 22 choices x 128 / 512 is 1.375 a held expert,
a quarter of the 5.5 that four chips' lanes give each expert."""
from benchmarks.harness.expert_share import emit_counts


def read(run):
    counts, sz = emit_counts(run), run["sizes"]
    if counts is None or not hasattr(sz, "held") \
            or not hasattr(sz, "of_kind"):
        return None
    slots = sz.held * len(sz.of_kind("E"))
    if not slots:
        return None
    return counts["moe_pairs"] / float(slots * counts["steps"])
