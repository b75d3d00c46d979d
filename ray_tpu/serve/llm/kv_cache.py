"""KV-cache page bookkeeping for the LLM engine.

The device-side page arrays are the model's, whatever class of
`ray_tpu.models.MODELS` it is (`models.paged.PagedDecoder.init_cache`:
keys and values per kv head, `models/decode.py`'s and `models/gqa.py`'s,
or one latent row a position, `models/latent.py`'s, whose page costs
`pool rows * page_size * row_width` whatever the heads; `models/paged.py`
has the addresses a table entry gives); this
module owns the host-side pool: which pages are free, which sequence
holds which pages, and how many pages a replica can afford given its
mesh shards. Pure Python so the tier-1 tests exercise alloc / free /
eviction without touching jax.

A model may keep something of a sequence for ever, whatever its length,
beside the pages that grow with it, and the allocator hands out pages of
two classes behind the one page table a sequence has. A sequence's first
`fixed` = `model.fixed_pages(page_size)` table entries are pages of the
*fixed class*, ids `0 .. fixed_pages - 1` with `fixed_pages = fixed x
sequences`; its later entries are of the class only the full layers' pool
backs, ids `fixed_pages .. num_pages - 1`. The full layers' pool (keys
and values, or the latent rows of a model whose growing pages are latent:
`models/hybrid_kda_moe.py`) backs both classes (logical page j of a
sequence at its table's entry j), and `num_pages` stays its count. What a fixed-class page names besides is the
model's:

- a ring: a window layer sees a sequence's last `window` positions, so it
  keeps a ring of `fixed` = `window_pages` pages a sequence, logical page j
  at table entry `j mod fixed`, in a pool of its own that holds exactly
  the fixed class (`models.paged.prefill_page_ids_held`);
- a state slot (`models.paged.StateSlots`): a recurrent layer (a delta
  rule's linear attention, a state-space layer's selective scan) keeps a
  state of one size, of whatever shape the model holds and prices
  (`cache_page_bytes(fixed=True)`), so `fixed` is 1 and a sequence's first
  table entry is also its *state slot*: the recurrent layers' pools are
  indexed by it.

A model that keeps nothing for ever has `fixed` 0 and the one class the
allocator always had.

**A page and a run.** A page is `page_size` positions: what a table entry
names, what a prefill writes whole and a decode step a row of, and it
stays that whatever follows. A *run* is `run` pages of the class that
grows whose ids lie behind one another from a multiple of `run` on, `[g x
run, g x run + run)`. A model class whose decode walk is bound by the
copies it starts and not by their bytes asks for one
(`models.paged.PagedDecoder.page_run`, from shapes alone, as its mixers
answer: the two sparse walks by their index keys' page,
`ops/sparse_attention.py`; the latent kernel by its rows' page, PR 64; the
per-head kernel by one pool's page, 16 KB asking for 4 and 8 KB for 8, PR
66, `ops/paged_attention.py`; 1 from 32 KB a pool on, over a ring, and
wherever a step gathers), the engine passes the answer on
(`PageAllocator(run=)`) and keeps no notion of a run of its own. The
allocator then hands the class out and takes it back in whole runs: what a
sequence holds of it is rounded up to whole runs (`alloc(1, held)` at a
run's end returns `run` ids, inside one none), so for every sequence and
every k the table's entries `fixed + k x run ..` that are held are `p, p +
1, ..` with `p % run == 0`, the pages no position has reached yet among
them: the sequence's own, masked by its length as the unused tail of a last
page is. What it costs: at most `run - 1` pages a sequence held ahead of
need, and the pages of the class that make no whole run (`unused_pages`,
under `run` at each end), which nobody gets.

**A table of a class that keeps a fixed page** (PR 66) is its `fixed`
entries, handed out page by page in any order, and then whole runs: the
runs open at table entry `fixed`, not 0, the walks are told `fixed` beside
`run` and copy the first `fixed` entries a page each and the rest a run
each, and the table is `fixed + ceil((pages - fixed) / run) x run` entries
wide (`ops.paged_attention.run_table_pages`, asked through
`PagedDecoder.table_pages`: the engine's `max_pages_per_seq`), so that a
sequence at full length fits with the last run it is handed. A ring's own
walk still reads the table's first `fixed` entries a page at a time. With
`run` 1 the free list, the order of ids and every answer are the
allocator's without runs.
"""
from __future__ import annotations

from typing import Dict, List, Optional


def pages_needed(n_positions: int, page_size: int) -> int:
    """Pages that cover n_positions cache slots."""
    return max(0, -(-n_positions // page_size))


def pages_from_budget(config, page_size: int, budget_bytes: int,
                      tp_shards: int = 1, dtype=None,
                      sequences: int = 0) -> int:
    """Pool size a per-shard HBM budget affords, by what the config's
    model says a page costs a shard: a cache of keys and values per head
    splits its kv heads across tp shards, so doubling tp doubles the
    pages the same per-chip budget buys; a latent cache does not. A model
    that keeps something of a sequence for ever (a ring of pages, a
    state) first pays for that, `sequences` (the decode lanes) x
    `fixed_pages` pages of the fixed class, each at what the model says it
    costs besides; the rest buys pages of the full layers' pool."""
    from ray_tpu.models import build_model
    model = build_model(config)
    per_page = model.cache_page_bytes(page_size, tp_shards=tp_shards,
                                      dtype=dtype)
    fixed = model.fixed_pages(page_size) * sequences
    if fixed:
        budget_bytes -= fixed * model.cache_page_bytes(
            page_size, tp_shards=tp_shards, dtype=dtype, fixed=True)
    return max(0, budget_bytes // per_page)


class PageAllocator:
    """Free-list allocator over a fixed pool of cache pages.

    Allocation is all-or-nothing (a sequence that cannot get every
    page it needs stays in the waiting queue rather than holding a
    partial claim that deadlocks the pool). Double-free is an error:
    a page returned twice would be handed to two sequences and corrupt
    both contexts silently.

    With `fixed` > 0 the pool has two classes (the module's docstring):
    the first `fixed` pages a sequence holds come from the fixed class,
    `fixed x sequences` pages (fewer where the pool is smaller), the rest
    from the other.

    With `run` > 1 the other class is handed out and taken back in whole
    runs, ids `[g x run, g x run + run)` (the module's docstring): what a
    sequence holds of it is rounded up to whole runs, and the pages of the
    class that make no whole run (`unused_pages`) are nobody's. At `run`
    1 every id, every order and every answer is the allocator's without
    runs.
    """

    def __init__(self, num_pages: int, fixed: int = 0, sequences: int = 0,
                 run: int = 1):
        if num_pages <= 0:
            raise ValueError(f"num_pages must be > 0, got {num_pages}")
        if run < 1:
            raise ValueError(f"a run is one page at least, got {run}")
        self.num_pages = num_pages
        self.fixed = int(fixed)
        self.run = int(run)
        self.fixed_pages = min(num_pages, self.fixed * int(sequences))
        # the first page of every free run, the lowest popped first
        first = -(-self.fixed_pages // self.run) * self.run
        self._free: List[int] = list(range(
            num_pages // self.run * self.run - self.run, first - 1,
            -self.run))
        self.unused_pages = (num_pages - self.fixed_pages
                             - len(self._free) * self.run)
        self._free_fixed: List[int] = list(range(self.fixed_pages - 1, -1, -1))
        self._held = set()
        self._held_of_run: Dict[int, int] = {}  # first page -> pages held

    @property
    def free_pages(self) -> int:
        return len(self._free) * self.run + len(self._free_fixed)

    @property
    def used_pages(self) -> int:
        return self.num_pages - self.unused_pages - self.free_pages

    @property
    def fixed_used(self) -> int:
        """Pages of the fixed class that sequences hold."""
        return self.fixed_pages - len(self._free_fixed)

    def _whole_runs(self, n: int) -> int:
        """n pages of the class that grows, rounded up to whole runs."""
        return -(-n // self.run) * self.run

    def fits(self, n: int) -> bool:
        """Whether a sequence alone in the pool could hold n pages."""
        first = min(n, self.fixed)
        return (first <= self.fixed_pages
                and self._whole_runs(n - first) <= self.num_pages
                - self.fixed_pages - self.unused_pages)

    def alloc(self, n: int, held: int = 0) -> Optional[List[int]]:
        """Claim n pages for a sequence that holds `held` already, or None
        (and claim nothing) if short: the pages that fill its table up to
        entry `fixed` from the fixed class, then the others, as many whole
        runs as bring what it holds of them up to whole runs (all of each
        run, so more than n at a run's start and none inside one)."""
        if n < 0:
            raise ValueError(f"cannot alloc {n} pages")
        first = min(n, max(0, self.fixed - held))
        grown = max(0, held - self.fixed)
        runs = (self._whole_runs(grown + n - first)
                - self._whole_runs(grown)) // self.run
        if first > len(self._free_fixed) or runs > len(self._free):
            return None
        pages = [self._free_fixed.pop() for _ in range(first)]
        for _ in range(runs):
            start = self._free.pop()
            self._held_of_run[start] = self.run
            pages.extend(range(start, start + self.run))
        self._held.update(pages)
        return pages

    def free(self, pages: List[int]) -> None:
        """Take back `pages`; a run is free again with its last page."""
        for p in pages:
            if p not in self._held:
                raise ValueError(
                    f"page {p} freed twice (or never allocated)")
            self._held.discard(p)
            if p < self.fixed_pages:
                self._free_fixed.append(p)
                continue
            start = p - p % self.run
            self._held_of_run[start] -= 1
            if not self._held_of_run[start]:
                del self._held_of_run[start]
                self._free.append(start)
