"""Cache positions the decode lanes held as a share of the positions the
decode program read (under the paged kernel each lane's live pages, whole,
so the loss is the last page's unused tail; under the einsum every lane's
whole table, `max_batch x max_pages_per_seq x page_size`, whatever the
lanes hold): `live_positions` over `read_positions`, summed over the traced
`engine.decode_dispatch` spans (the engine's counters `kv_positions_live`
/ `kv_positions_read`)."""
from benchmarks.harness.spans import DISPATCH, of_run


def read(run):
    r = of_run(run)
    read_positions = r.attr_sum(DISPATCH, "read_positions") if r else 0
    if not read_positions:
        return None
    return 100.0 * r.attr_sum(DISPATCH, "live_positions") / read_positions
