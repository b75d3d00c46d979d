"""Roofline share of the paged decode-attention kernel over the traced
steps: what the algorithm has to move for the positions the lanes held
(`required_ops.paged_decode_call`: every live position's key and value
read once a layer, queries in, outputs out; bytes bound it at 819 GB/s)
over the device time of the events called `paged_decode_attn`
(ops/paged_attention.py, one a layer and step). Live positions and lanes
are the sums of `engine.decode_dispatch`'s attributes over the same traced
span; a step dispatched at its edge is one in some 450. Reads the model's
`layers`, `kv_dim` and `q_dim`, so it is listed for cells whose model has
them."""
from benchmarks.harness.required_ops import paged_decode_call, roofline_share
from benchmarks.harness.spans import DISPATCH, kernel_calls, of_run


def read(run):
    found, r = kernel_calls(run, ["paged_decode_attn"]), of_run(run)
    if found is None or r is None or not r.named(DISPATCH):
        return None
    z = run["sizes"]
    need = paged_decode_call(r.attr_sum(DISPATCH, "live_positions"),
                             r.attr_sum(DISPATCH, "lanes"), z.layers,
                             z.kv_dim, z.q_dim)
    return roofline_share(need["flops"], need["bytes"], found[1],
                          run["peaks"])
