"""Roofline share of the latent decode-attention kernel over the traced
steps: what the absorbed algorithm has to move for the positions the lanes
held (the model module's `mla_decode_call`: every live position's 576-number
row read once a layer and used as key and as value, queries in, latent
outputs out; bytes bound it at 819 GB/s) over the device time of the events
called `mla_paged_decode_attn` (ops/paged_attention.py, one a layer and
step). Live positions and lanes are the sums of `engine.decode_dispatch`'s
attributes over the same traced span. The pool's rows are padded to 640
numbers and a page's tail is read whole: both are the program's cost and
lower this share. Listed for cells whose model module has
`mla_decode_call`."""
from benchmarks.harness.decode_events import KERNEL_MLA
from benchmarks.harness.required_ops import roofline_share
from benchmarks.harness.spans import DISPATCH, kernel_calls, of_run


def read(run):
    need_of = getattr(run["model"], "mla_decode_call", None)
    found, r = kernel_calls(run, [KERNEL_MLA]), of_run(run)
    if need_of is None or found is None or r is None \
            or not r.named(DISPATCH):
        return None
    need = need_of(run["sizes"], r.attr_sum(DISPATCH, "live_positions"),
                   r.attr_sum(DISPATCH, "lanes"))
    return roofline_share(need["flops"], need["bytes"], found[1],
                          run["peaks"])
