"""Held experts that got at least one row, as a share of the experts held,
over the traced decode steps: the engine's counter `moe_experts_touched`
over `held experts x expert layers x steps`, the expert layers counted from
the model module's own `Sizes` (`moe.experts_touched_share.reason4k`
divides by every layer). It is the share of the held expert weights a decode
step has to read: at 1.375 rows a held expert some 75 % when the routing is
even."""
from benchmarks.harness.expert_share import emit_counts


def read(run):
    counts, sz = emit_counts(run), run["sizes"]
    if counts is None or not hasattr(sz, "held") \
            or not hasattr(sz, "of_kind"):
        return None
    slots = sz.held * len(sz.of_kind("E"))
    if not slots:
        return None
    return 100.0 * counts["moe_experts_touched"] / (slots * counts["steps"])
