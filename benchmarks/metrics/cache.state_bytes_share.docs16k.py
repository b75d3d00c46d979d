"""The share of a decode step's cache traffic that is recurrent state:
`state_bytes` (what the KDA layers move for the lanes, a state and a
convolution tail a layer, read and written, whatever the lanes' lengths)
over `state_bytes` plus the bytes of the latent rows the latent layers read
for the same lanes (`read_positions` x the row's `kv_lora + rope` numbers
x 2 bytes x the latent layers held, the width from the model module's
`Sizes`), summed over the traced `engine.decode_dispatch` spans.
`cache.state_bytes_share.answers3k` and `.agent8k` are the same reading
beside keys and values a head; here what grows with a sequence is one
latent row a position in one layer of seven. None for a program that
writes no `state_bytes` or a model module without a latent row."""
from benchmarks.harness.spans import DISPATCH, of_run

KEY = "state_bytes"


def read(run):
    r = of_run(run)
    steps = [s for s in r.named(DISPATCH) if KEY in s.stats] if r else []
    state = sum(int(s.stats[KEY]) for s in steps)
    sz = run["sizes"]
    if not state or not hasattr(sz, "cache_row") \
            or not hasattr(sz, "attentions"):
        return None
    per_position = sz.cache_row * 2 * sz.attentions
    rows = per_position * sum(int(s.stats["read_positions"]) for s in steps)
    return 100.0 * state / (state + rows)
