"""Percent of the positions the indexer scored that the attention then read,
over the traced decode steps: the engine's counters `dsa_positions_selected`
over `dsa_positions_scored`, both counted on the device (a layer and lane:
the positions held, and min(held, 2048) of them) and read from the
`engine.emit` spans. 100 would be dense attention; the cell's lanes hold
2.6k to 16k positions, so about a third. None for a program that writes no
such count."""
from benchmarks.harness.dsa_events import emit_counts


def read(run):
    counts = emit_counts(run)
    if counts is None or not counts["dsa_positions_scored"]:
        return None
    return (100.0 * counts["dsa_positions_selected"]
            / counts["dsa_positions_scored"])
