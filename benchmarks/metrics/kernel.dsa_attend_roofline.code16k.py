"""Roofline share of the attention over the chosen rows, over the traced
decode steps: what the absorbed algorithm has to move for the rows the
indexer chose (the model module's `dsa_attend_call`: min(live, 2048) rows of
1,152 bytes a lane and layer read once and used as key and as value, the
lanes' queries in and latent outputs out; bytes bound it) over the device
time of `r.attn_core` in the executions of `jit__step`. The rows are
`dsa_positions_selected`, counted on the device in those very steps and read
from the `engine.emit` spans, the lanes `engine.decode_dispatch`'s; both
scaled by the executions counted over the spans counted. A row's padding to
640 numbers, and anything an implementation reads beyond the chosen rows,
lowers this share and cannot raise it."""
from benchmarks.harness.dsa_events import (CORE, STEP, emit_counts,
                                           region_seconds)
from benchmarks.harness.required_ops import roofline_share
from benchmarks.harness.spans import DISPATCH, of_run


def read(run):
    need_of = getattr(run["model"], "dsa_attend_call", None)
    found, counts, r = (region_seconds(run, STEP, (CORE,)),
                        emit_counts(run), of_run(run))
    if need_of is None or found is None or counts is None \
            or not counts["dsa_positions_selected"] or not r.named(DISPATCH):
        return None
    sz = run["sizes"]
    lanes = r.attr_sum(DISPATCH, "lanes") / float(len(r.named(DISPATCH)))
    selected = counts["dsa_positions_selected"] / float(counts["steps"])
    need = need_of(sz, selected * found[0], lanes * sz.layers * found[0])
    return roofline_share(need["flops"], need["bytes"], found[1],
                          run["peaks"])
