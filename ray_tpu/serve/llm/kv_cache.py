"""KV-cache page bookkeeping for the LLM engine.

The device-side page arrays are the model's (`model.init_cache`:
`ray_tpu.models.decode`'s keys and values per kv head for `Transformer`,
one latent row a position for `MLAMoE`, whose page costs
`layers * page_size * row_width` whatever the heads); this
module owns the host-side pool: which pages are free, which sequence
holds which pages, and how many pages a replica can afford given its
mesh shards. Pure Python so the tier-1 tests exercise alloc / free /
eviction without touching jax.

A model whose layers are of two kinds (`GQAWindowMoE`: full attention and
a sliding window) holds two pools behind the one page table a sequence
has, and the allocator hands out pages of two classes. A window layer sees
a sequence's last `window` positions, so it keeps `ring` =
`model.window_pages(page_size)` pages a sequence for ever, logical page j at
table entry `j mod ring`; a full layer keeps every page, logical page j at
entry j. So a sequence's first `ring` table entries are pages of the
*ring class*, ids `0 .. ring_pages - 1` with `ring_pages = ring x
sequences`, which both pools back (the window layers' pool holds exactly
these); its later entries are of the class only the full layers' pool
backs, ids `ring_pages .. num_pages - 1`. `num_pages` stays the full
layers' count. A model without window layers has `ring` 0 and the one
class the allocator always had.
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
    with window layers first pays for their fixed ring, `sequences` (the
    decode lanes) x `window_pages` pages of the ring class; the rest buys
    pages of the full layers' pool."""
    from ray_tpu.models import build_model
    model = build_model(config)
    per_page = model.cache_page_bytes(page_size, tp_shards=tp_shards,
                                      dtype=dtype)
    ring = model.window_pages(page_size) * sequences
    if ring:
        budget_bytes -= ring * model.cache_page_bytes(
            page_size, tp_shards=tp_shards, dtype=dtype, ring=True)
    return max(0, budget_bytes // per_page)


class PageAllocator:
    """Free-list allocator over a fixed pool of cache pages.

    Allocation is all-or-nothing (a sequence that cannot get every
    page it needs stays in the waiting queue rather than holding a
    partial claim that deadlocks the pool). Double-free is an error:
    a page returned twice would be handed to two sequences and corrupt
    both contexts silently.

    With `ring` > 0 the pool has two classes (the module's docstring):
    the first `ring` pages a sequence holds come from the ring class,
    `ring x sequences` pages (fewer where the pool is smaller), the rest
    from the other.
    """

    def __init__(self, num_pages: int, ring: int = 0, sequences: int = 0):
        if num_pages <= 0:
            raise ValueError(f"num_pages must be > 0, got {num_pages}")
        self.num_pages = num_pages
        self.ring = int(ring)
        self.ring_pages = min(num_pages, self.ring * int(sequences))
        self._free: List[int] = list(range(num_pages - 1,
                                           self.ring_pages - 1, -1))
        self._free_ring: List[int] = list(range(self.ring_pages - 1, -1, -1))
        self._held = set()

    @property
    def free_pages(self) -> int:
        return len(self._free) + len(self._free_ring)

    @property
    def used_pages(self) -> int:
        return self.num_pages - self.free_pages

    @property
    def ring_used(self) -> int:
        """Pages of the ring class that sequences hold."""
        return self.ring_pages - len(self._free_ring)

    def fits(self, n: int) -> bool:
        """Whether a sequence alone in the pool could hold n pages."""
        first = min(n, self.ring)
        return (first <= self.ring_pages
                and n - first <= self.num_pages - self.ring_pages)

    def alloc(self, n: int, held: int = 0) -> Optional[List[int]]:
        """Claim n pages for a sequence that holds `held` already, or None
        (and claim nothing) if short: the pages that fill its table up to
        entry `ring` from the ring class, then the others."""
        if n < 0:
            raise ValueError(f"cannot alloc {n} pages")
        first = min(n, max(0, self.ring - held))
        if first > len(self._free_ring) or n - first > len(self._free):
            return None
        pages = ([self._free_ring.pop() for _ in range(first)]
                 + [self._free.pop() for _ in range(n - first)])
        self._held.update(pages)
        return pages

    def free(self, pages: List[int]) -> None:
        for p in pages:
            if p not in self._held:
                raise ValueError(
                    f"page {p} freed twice (or never allocated)")
            self._held.discard(p)
            (self._free_ring if p < self.ring_pages
             else self._free).append(p)
