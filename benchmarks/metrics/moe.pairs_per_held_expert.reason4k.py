"""Rows a held expert gets a layer and decode step, over the traced steps:
the engine's counter `moe_pairs` over `held experts x layers x steps`. It
says how near the cell is to the deployment's expert load: 32 lanes x 12
choices x 16 / 768 slots is 0.5 a held expert, what a 32-chip deployment
that decodes one lane a chip gives each of its experts."""
from benchmarks.harness.expert_share import emit_counts, held_slots


def read(run):
    counts, slots = emit_counts(run), held_slots(run)
    if counts is None or not slots:
        return None
    return counts["moe_pairs"] / float(slots * counts["steps"])
