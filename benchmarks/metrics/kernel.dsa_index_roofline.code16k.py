"""Roofline share of the indexer over the traced decode steps: what the
algorithm has to move to score the positions the lanes held (the model
module's `dsa_index_call`: every live position's 256-byte index key read once
a layer, the lanes' index queries and head weights in, a float32 score a
position out, 32 x 128 x 2 operations a position; bytes bound it at 819 GB/s)
over the device time of `r.attn_index` in the executions of `jit__step`. Live
positions and lanes are the sums of `engine.decode_dispatch`'s attributes
over the same traced span, scaled by the executions counted over the spans
counted (a step dispatched at the trace's edge has its span on one side and
its operations on the other). The index projections' weights, a table's
unassigned pages and the choice's own passes are the program's cost, which
lowers this share and cannot raise it."""
from benchmarks.harness.dsa_events import INDEX, STEP, region_seconds
from benchmarks.harness.required_ops import roofline_share
from benchmarks.harness.spans import DISPATCH, of_run


def read(run):
    need_of = getattr(run["model"], "dsa_index_call", None)
    found, r = region_seconds(run, STEP, (INDEX,)), of_run(run)
    if need_of is None or found is None or r is None \
            or not r.named(DISPATCH):
        return None
    need = need_of(run["sizes"], r.attr_sum(DISPATCH, "live_positions"),
                   r.attr_sum(DISPATCH, "lanes"))
    share = found[0] / float(len(r.named(DISPATCH)))
    return roofline_share(share * need["flops"], share * need["bytes"],
                          found[1], run["peaks"])
