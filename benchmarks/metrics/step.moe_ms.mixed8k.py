"""Device milliseconds a decode step spends in its expert layers' feed-
forward: routing, the sort of the pairs, the three grouped matmuls, the
combine and the shared expert, as `step.moe_ms.batch32` finds it.

An operation in a device trace carries no scope, so the feed-forward is
found between two kernels that do: in one execution of `jit__step`, from
the end of a layer's attention kernel (`paged_decode_attn` on a full layer,
`paged_window_decode_attn` on a sliding one, merged in time order: one a
layer) to the start of the next layer's. That span also holds the
attention's gate and output projection and the next layer's input
projections (some 60 MB of weights against 0.9-1.0 GB of touched experts at
the published sizes: about 6 % too much, stated here and not taken out).
The layers whose `mlp_layer_types` entry is `dense` are left out; the last
layer has no next layer (the head follows it) and is taken as the mean of
the other expert layers. Median over the traced steps."""
import statistics

from benchmarks.harness.decode_events import kernels_by_step

KERNELS = ("paged_decode_attn", "paged_window_decode_attn")


def read(run):
    kinds = run["cfg"].get("mlp_layer_types")
    found = [kernels_by_step(run, k) for k in KERNELS]
    if kinds is None or any(f is None for f in found):
        return None
    sparse = [i for i, k in enumerate(kinds) if k == "sparse"]
    per_step = []
    for per_kind in zip(*found):
        evs = sorted((e for k in per_kind for e in k),
                     key=lambda e: e.start)
        if len(evs) != len(kinds):
            continue
        after = [b.start - a.end for a, b in zip(evs, evs[1:])]
        spans = [after[i] for i in sparse if i < len(after)]
        if spans:
            per_step.append(sum(spans) / len(spans) * len(sparse))
    return 1e3 * statistics.median(per_step) if per_step else None
