"""Cache positions a sliding layer's decode attention read as a share of
what a full layer's read for the same lanes: `window_positions_read` over
`read_positions`, summed over the traced `engine.decode_dispatch` spans
(the engine's counters `kv_window_positions_read` / `kv_positions_read`).
It is the share of a whole read that the window costs: a sliding layer
holds a lane's last 528 positions in a ring however long the lane grows,
so at 4,000 positions a lane it reads about 13 %; held as a whole cache it
would read 100 %. None for a program that writes no
`window_positions_read` (a model without window layers, the parent of
PR 35)."""
from benchmarks.harness.spans import DISPATCH, of_run

KEY = "window_positions_read"


def read(run):
    r = of_run(run)
    steps = [s for s in r.named(DISPATCH) if KEY in s.stats] if r else []
    whole = sum(int(s.stats["read_positions"]) for s in steps)
    if not whole:
        return None
    return 100.0 * sum(int(s.stats[KEY]) for s in steps) / whole
