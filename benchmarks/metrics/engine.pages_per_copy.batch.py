"""Pages one copy of the decode walk over the growing pool brings: the
positions the decode program read, in pages, over the copies its walk
started (`read_positions / page_size / walk_copies`, one layer, one pool,
summed over the traced `engine.decode_dispatch` spans; the engine's counters
`kv_positions_read` / `kv_walk_copies`). 1.0 where a walk copies a page at a
time; the length of the allocator's runs where a model class asked for its
pages in runs and its walk brings a run a copy (`PagedDecoder.page_run`).
None where no span carries `walk_copies` (a program from before PR 62) or
the step ran no walk (the gather)."""
from benchmarks.harness.spans import DISPATCH, of_run


def read(run):
    r = of_run(run)
    spans = [s for s in r.named(DISPATCH)
             if "walk_copies" in s.stats] if r else []
    copies = sum(int(s.stats["walk_copies"]) for s in spans)
    if not copies:
        return None
    page_size = run["cfg"]["deployment"]["page_size"]
    return sum(int(s.stats["read_positions"])
               for s in spans) / float(page_size * copies)
