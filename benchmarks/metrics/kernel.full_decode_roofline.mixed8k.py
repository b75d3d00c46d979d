"""Roofline share of the full layers' paged decode-attention kernel over the
traced steps: what the algorithm has to move for the positions the lanes
held (the model module's `full_decode_call`: every live position's key and
value read once a full layer, queries in, outputs out; bytes bound it at
819 GB/s) over the device time of the events called `paged_decode_attn`
(ops/paged_attention.py, one a full layer and step). Live positions and
lanes are the sums of `engine.decode_dispatch`'s `live_positions` and
`lanes` over the same traced span. A page's unused tail is read whole: the
program's cost, which lowers this share. Listed for cells whose model
module has `full_decode_call`."""
from benchmarks.harness.required_ops import roofline_share
from benchmarks.harness.spans import DISPATCH, kernel_calls, of_run


def read(run):
    need_of = getattr(run["model"], "full_decode_call", None)
    found, r = kernel_calls(run, ["paged_decode_attn"]), of_run(run)
    if need_of is None or found is None or r is None \
            or not r.named(DISPATCH):
        return None
    need = need_of(run["sizes"], r.attr_sum(DISPATCH, "live_positions"),
                   r.attr_sum(DISPATCH, "lanes"))
    return roofline_share(need["flops"], need["bytes"], found[1],
                          run["peaks"])
