"""The share of a decode step's cache traffic that is recurrent state:
`state_bytes` (what the linear layers move for the lanes, a state and a
convolution tail a layer, read and written, whatever the lanes' lengths)
over `state_bytes` plus the bytes of the keys and values the full layers
read for the same lanes (`read_positions` x 2 x the kv width x 2 bytes x
the full layers), summed over the traced `engine.decode_dispatch` spans
(the engine's counters `state_bytes_moved`, `kv_positions_read`). The
state's bytes do not grow with the context and the keys' and values' do, so
a longer context lowers this share. None for a program that writes no
`state_bytes` (a model without linear layers, the parent of PR 37)."""
from benchmarks.harness.spans import DISPATCH, of_run

KEY = "state_bytes"


def read(run):
    r = of_run(run)
    steps = [s for s in r.named(DISPATCH) if KEY in s.stats] if r else []
    state = sum(int(s.stats[KEY]) for s in steps)
    if not state:
        return None
    sz = run["sizes"]
    per_position = 2 * sz.kv_dim * 2 * len(sz.of_kind("full_attention"))
    kv = per_position * sum(int(s.stats["read_positions"]) for s in steps)
    return 100.0 * state / (state + kv)
