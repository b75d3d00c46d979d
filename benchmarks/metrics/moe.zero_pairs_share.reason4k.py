"""Choices that fell on a slot that computes nothing, as a share of every
choice the traced decode steps' lanes made (lanes x 12 x layers: the
engine's counters `moe_zero_pairs` over `moe_pairs + moe_zero_pairs +
moe_away_pairs`, counted by the model on the device and read from the
`engine.emit` spans). A third when the routing is even (256 of 768 slots);
the share of a token's expert compute the model's router gives away."""
from benchmarks.harness.expert_share import emit_counts


def read(run):
    counts = emit_counts(run)
    if counts is None or not counts["all_pairs"]:
        return None
    return 100.0 * counts["moe_zero_pairs"] / counts["all_pairs"]
