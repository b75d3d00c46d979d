"""The share of a decode step's cache traffic that is recurrent state:
`state_bytes` (what the state-space layers move for the lanes, a state and
a convolution tail a layer, read and written, whatever the lanes' lengths)
over `state_bytes` plus the bytes of the keys and values the attention
layers read for the same lanes (`read_positions` x 2 x the kv width x 2
bytes x the attention layers), summed over the traced
`engine.decode_dispatch` spans. `cache.state_bytes_share.answers3k` is the
same reading for a model module that names its layers `full_attention`; this
one counts the layers the pattern calls `*`. None for a program that writes
no `state_bytes`."""
from benchmarks.harness.spans import DISPATCH, of_run

KEY = "state_bytes"


def read(run):
    r = of_run(run)
    steps = [s for s in r.named(DISPATCH) if KEY in s.stats] if r else []
    state = sum(int(s.stats[KEY]) for s in steps)
    sz = run["sizes"]
    if not state or not hasattr(sz, "of_kind"):
        return None
    per_position = 2 * sz.kv_dim * 2 * len(sz.of_kind("*"))
    kv = per_position * sum(int(s.stats["read_positions"]) for s in steps)
    return 100.0 * state / (state + kv)
