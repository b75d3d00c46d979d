"""Percent of the cache positions a decode step's walks copy in that are a
ring's: `ring_positions_read` (whole pages from the first the window
reaches, summed over the sliding layers, counted on the device and read from
the `engine.emit` spans) over itself plus what the full layers' walks read
(`engine.decode_dispatch`'s `read_positions`, a layer whose cache is whole,
times the full layers), both a step over the traced span. Three layers of
five read a ring here, and it is what they cost beside the two that read
all a lane holds: held as whole caches the sliding layers would read three
fifths. None for a program that writes no `ring_positions_read`."""
from benchmarks.harness.ring_events import dispatch_mean, emit_counts


def read(run):
    counts, whole = emit_counts(run), dispatch_mean(run, "read_positions")
    if counts is None or not whole:
        return None
    ring = counts["ring_positions_read"] / float(counts["steps"])
    full = whole * len(run["sizes"].of_kind("full_attention"))
    return 100.0 * ring / (ring + full)
