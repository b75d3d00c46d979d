"""Held experts that got at least one row, as a share of the experts held,
over the traced decode steps: the engine's counter `moe_experts_touched`
over `held experts x layers x steps`. It is the share of the held expert
weights a decode step has to read: at 0.5 rows a held expert some 6.3 of 16
when the routing is even. (`moe.experts_touched_share.batch32` reads the
keys of a model that holds every expert of its router.)"""
from benchmarks.harness.expert_share import emit_counts, held_slots


def read(run):
    counts, slots = emit_counts(run), held_slots(run)
    if counts is None or not slots:
        return None
    return 100.0 * counts["moe_experts_touched"] / (slots * counts["steps"])
