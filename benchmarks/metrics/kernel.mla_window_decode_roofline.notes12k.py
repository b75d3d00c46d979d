"""Roofline share of the latent decode kernel over a ring, over the traced
steps: what the absorbed algorithm has to move for the positions inside the
lanes' windows (the model module's `window_latent_decode_call`: each of a
lane's last `min(length, 513)` rows of 1,088 numbers read once a sliding
layer and used as key and as value, the lanes' queries in and latent outputs
out; bytes bound it at 819 GB/s) over the device time of the events called
`mla_paged_window_decode_attn` (ops/paged_attention.py, one a sliding layer
and step). The rows are `ring_positions_seen`, counted on the device in the
traced steps and read from the `engine.emit` spans, the lanes
`engine.decode_dispatch`'s; both a step, times the kernel's calls over the
sliding layers. The ring's first and last page read whole (up to 544
positions for a window of 513) and a row's padding to 1,152 numbers are the
program's cost, which lowers this share. None for a program without the
kernel or the counts (the parent of PR 65)."""
from benchmarks.harness.required_ops import roofline_share
from benchmarks.harness.ring_events import KERNEL, dispatch_mean, emit_counts
from benchmarks.harness.spans import kernel_calls


def read(run):
    need_of = getattr(run["model"], "window_latent_decode_call", None)
    found, counts = kernel_calls(run, [KERNEL]), emit_counts(run)
    lanes = dispatch_mean(run, "lanes")
    if need_of is None or found is None or counts is None or lanes is None \
            or not counts["ring_positions_seen"]:
        return None
    sliding = len(run["sizes"].of_kind("sliding_attention"))
    steps = found[0] / float(sliding)       # the steps the calls make up
    seen = counts["ring_positions_seen"] / float(counts["steps"])
    need = need_of(run["sizes"], seen * steps, lanes * found[0])
    return roofline_share(need["flops"], need["bytes"], found[1],
                          run["peaks"])
