"""Percent of a decode step's lanes whose full layers read every position
they hold: lanes at or under `index_topk` (2,048) over all lanes, from
`dsa_lanes_past_topk` (counted on the device once a full layer, read from
the `engine.emit` spans) and `engine.decode_dispatch`'s `lanes`, both a step
over the traced span. It tells the cell's two regimes apart: a lane under
2,048 is dense latent attention with a gate, one past it reads 2,048 of what
it scored. None for a program that writes no `ring_*` count (a class whose
every lane is past `index_topk` has `dsa.selected_share.code16k`)."""
from benchmarks.harness.ring_events import dispatch_mean, emit_counts


def read(run):
    counts, lanes = emit_counts(run), dispatch_mean(run, "lanes")
    if counts is None or not lanes:
        return None
    full = len(run["sizes"].of_kind("full_attention"))
    past = counts["dsa_lanes_past_topk"] / float(counts["steps"] * full)
    return 100.0 * (1.0 - past / lanes)
