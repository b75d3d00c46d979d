"""Roofline share of the sliding layers' paged decode-attention kernel over
the traced steps: what the algorithm has to move for the positions inside
the lanes' windows (the model module's `window_decode_call`: the key and
value of each of a lane's last `min(length, 512)` positions read once a
sliding layer, queries in, outputs out; bytes bound it) over the device
time of the events called `paged_window_decode_attn`
(ops/paged_attention.py, one a sliding layer and step). The positions are
the sum of `engine.decode_dispatch`'s `window_positions_live` over the same
traced span, which only a program with window layers writes: the parent of
PR 35 gives None. The ring's first and last page are read whole (512 to 528
positions for a window of 512): the program's cost, which lowers this
share."""
from benchmarks.harness.required_ops import roofline_share
from benchmarks.harness.spans import DISPATCH, kernel_calls, of_run

KEY = "window_positions_live"


def read(run):
    need_of = getattr(run["model"], "window_decode_call", None)
    found, r = kernel_calls(run, ["paged_window_decode_attn"]), of_run(run)
    if need_of is None or found is None or r is None:
        return None
    steps = [s for s in r.named(DISPATCH) if KEY in s.stats]
    if not steps:
        return None
    need = need_of(run["sizes"], sum(int(s.stats[KEY]) for s in steps),
                   sum(int(s.stats["lanes"]) for s in steps))
    return roofline_share(need["flops"], need["bytes"], found[1],
                          run["peaks"])
