"""Device milliseconds a decode step spends in its expert layers' feed-
forward: routing, the sort of the pairs, the three grouped matmuls, the
combine and the shared expert.

An operation in a device trace carries no scope, so the feed-forward is
found between two kernels that do: in one execution of `jit__step`, from
the end of a layer's `mla_paged_decode_attn` to the start of the next
layer's. That span also holds the attention's two output projections and
the next layer's input projections (44 MB of weights against 1.05 GB of
touched experts at the published sizes: some 4 % too much, stated here and
not taken out). The first span is the dense layer's and is left out; the
last expert layer has no next layer (the head follows it) and is taken as
the mean of the others. Median over the traced steps."""
import statistics

from benchmarks.harness.decode_events import KERNEL_MLA, kernels_by_step


def read(run):
    by_step = kernels_by_step(run, KERNEL_MLA)
    first_dense = run["cfg"].get("first_k_dense_replace")
    if by_step is None or first_dense is None:
        return None
    layers = run["cfg"]["num_hidden_layers"]
    per_step = []
    for evs in by_step:
        if len(evs) != layers:
            continue
        spans = [b.start - a.end for a, b in zip(evs, evs[1:])][first_dense:]
        if spans:
            per_step.append(sum(spans) / len(spans) * (layers - first_dense))
    return 1e3 * statistics.median(per_step) if per_step else None
