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
"""
from __future__ import annotations

from typing import List, Optional


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
    """

    def __init__(self, num_pages: int, fixed: int = 0, sequences: int = 0):
        if num_pages <= 0:
            raise ValueError(f"num_pages must be > 0, got {num_pages}")
        self.num_pages = num_pages
        self.fixed = int(fixed)
        self.fixed_pages = min(num_pages, self.fixed * int(sequences))
        self._free: List[int] = list(range(num_pages - 1,
                                           self.fixed_pages - 1, -1))
        self._free_fixed: List[int] = list(range(self.fixed_pages - 1, -1, -1))
        self._held = set()

    @property
    def free_pages(self) -> int:
        return len(self._free) + len(self._free_fixed)

    @property
    def used_pages(self) -> int:
        return self.num_pages - self.free_pages

    @property
    def fixed_used(self) -> int:
        """Pages of the fixed class that sequences hold."""
        return self.fixed_pages - len(self._free_fixed)

    def fits(self, n: int) -> bool:
        """Whether a sequence alone in the pool could hold n pages."""
        first = min(n, self.fixed)
        return (first <= self.fixed_pages
                and n - first <= self.num_pages - self.fixed_pages)

    def alloc(self, n: int, held: int = 0) -> Optional[List[int]]:
        """Claim n pages for a sequence that holds `held` already, or None
        (and claim nothing) if short: the pages that fill its table up to
        entry `fixed` from the fixed class, then the others."""
        if n < 0:
            raise ValueError(f"cannot alloc {n} pages")
        first = min(n, max(0, self.fixed - held))
        if first > len(self._free_fixed) or n - first > len(self._free):
            return None
        pages = ([self._free_fixed.pop() for _ in range(first)]
                 + [self._free.pop() for _ in range(n - first)])
        self._held.update(pages)
        return pages

    def free(self, pages: List[int]) -> None:
        for p in pages:
            if p not in self._held:
                raise ValueError(
                    f"page {p} freed twice (or never allocated)")
            self._held.discard(p)
            (self._free_fixed if p < self.fixed_pages
             else self._free).append(p)
