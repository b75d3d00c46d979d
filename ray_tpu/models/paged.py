"""What a serving engine asks of a model, written down once
(`PagedDecoder`: `serve/llm/engine.py` calls it and names no class), and
the paged cache's address arithmetic, which every class that serves reads
and writes by.

**The cache's addresses.** A sequence's page table names, entry by entry,
the pool page that holds its positions `j * page_size ..`; an entry of -1
is unassigned. A decode lane at `position` writes entry `position //
page_size` at row `position % page_size` and sees `position + 1` positions
(`lane_page`, `decode_lanes`); a padded prompt fills the table's leading
entries, the pages wholly past its true length dropped (`prefill_page_ids`;
`prefill_page_ids_held` is the same set by the count of pages held, which a
ring of pages needs). A write that must not happen (an inactive lane, an
unassigned entry, a page past the prompt) goes to the index one past the
pool, which `mode="drop"` discards. A mixer that keeps a state of fixed
size a sequence (a `Pool` of kind SLOT) keeps it at the slot the table's
**first** entry names, a page of the allocator's fixed class
(`serve/llm/kv_cache.py`); the pools have one slot more than the class,
nobody's, for what a kernel must put somewhere. `ExpertCounts` is what a
class with expert layers keeps of them in its cache.

**A mixer answers for itself** (`Mixer`): the leaves of its part of a
layer, the pools it keeps of a sequence (`Pool`: rows in pages that grow
with it, rows in a ring of pages, or one slot), the kernel that reads them
and its three forwards. **A served class is a table of them** (`Layer`, a
row a layer: the mixers that read the normed stream and are summed, the
feed-forward that follows), and `PagedDecoder` walks the table: one
`hidden`, one `prefill`, one `decode_step`, one `init_cache` and one of
each byte and kernel ask, whatever the mixers are.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import regions as R
from ray_tpu.models.moe import STEP_COUNTS, step_counts
from ray_tpu.ops import paged_attention as _paged
from ray_tpu.ops.conv import fold_tail
from ray_tpu.ops.losses import softmax_cross_entropy
from ray_tpu.ops.norms import rms_norm

Params = Dict[str, Any]
Cache = Dict[str, Any]


def _is_shape(node) -> bool:
    return isinstance(node, tuple)


def fill(key: jax.Array, shapes, dtype):
    """A tree (dicts and lists) of `(shape, init std)` leaves -> the same
    tree of arrays: a normal draw in float32 times `std`, zeros where `std`
    is 0. The leaves take the keys of one split in the order the tree names
    them, so a class that keeps its names' order keeps its arrays."""
    keys = iter(jax.random.split(key, len(jax.tree_util.tree_leaves(
        shapes, is_leaf=_is_shape))))

    def make(node):
        if isinstance(node, dict):
            return {name: make(child) for name, child in node.items()}
        if isinstance(node, list):
            return [make(child) for child in node]
        shape, std = node
        k = next(keys)
        return ((jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
                if std else jnp.zeros(shape, dtype))

    return make(shapes)


# what a sequence holds of a pool: rows in pages that grow with it, rows in
# a ring of pages (the allocator's fixed class), or one slot of that class
PAGED, RING, SLOT = "paged", "ring", "slot"


@dataclasses.dataclass(frozen=True)
class Pool:
    """One array of the cache as the mixer that keeps it declares it, a row
    of it a layer that has the mixer: PAGED `(layers, num_pages, page_size,
    *row)`, `row` a position's; RING the same over the fixed class's pages,
    of which a sequence holds `ring_pages(window)`; SLOT `(layers, slots +
    1, *row)`, `row` all a sequence keeps, at the slot its first table entry
    names, and one slot more, nobody's."""
    name: str
    kind: str
    row: Tuple[int, ...]
    dtype: Any = None           # None: the cache's
    # whether tp shards split a row among them (keys and values a head; a
    # latent is shared by every head, so a shard holds it whole)
    split: bool = False
    window: int = 0             # RING: the positions its layers see
    # whether its rows are the engine's `pool_rows` (a row an attention)
    an_attention: bool = False

    def zeros(self, layers: int, num_pages: int, page_size: int,
              fixed_pages: int, dtype) -> jax.Array:
        """The pool of `layers` layers, zeroed (inside `init_cache`'s
        jit)."""
        lead = {PAGED: (num_pages, page_size),
                RING: (max(fixed_pages, 1), page_size),
                SLOT: (fixed_pages + 1,)}[self.kind]
        return jnp.zeros((layers, *lead, *self.row), self.dtype or dtype)

    def bytes(self, dtype, page_size: int = 1, tp_shards: int = 1) -> int:
        """Of one layer's page (of its slot, at a `page_size` of 1) on a
        shard."""
        width = self.row[-1] // (max(1, tp_shards) if self.split else 1)
        return (page_size * math.prod(self.row[:-1]) * width
                * jnp.dtype(self.dtype or dtype).itemsize)


class Walk:
    """What the layers of one traced program share: `lanes` (a decode
    step's positions (B,); None where sequences start at 0), `true_len` (a
    padded prompt's), where a pool's kind is written (`pages[kind]`: a
    prompt's page ids; a step's (page a lane, the tables its kernel walks)),
    `offset` and `lengths` (a step's row and reach a lane), `slot`, `run`
    and `fixed` (a step's: what the class answered the engine's allocator
    with, `PagedDecoder.page_run` and `fixed_pages`, so that a kernel
    copies runs only over tables laid in them: the first `fixed` entries a
    page each, of the fixed class, then whole runs of `run`) and `tables`,
    what a mixer's `open` made once for all its layers."""
    slot = offset = lengths = None
    run, fixed = 1, 0

    def __init__(self, sequences=None, lanes=None, true_len=None):
        self._sequences, self.lanes, self.true_len = sequences, lanes, true_len
        self.tables, self.pages = {}, {}

    def positions(self) -> jax.Array:
        """The program's positions as a rotary table made once takes them:
        the lanes', or what `sequences()` makes at the first ask ((b, s) of
        whole sequences, (1, s) of a prompt), so that a class without such
        a table traces none."""
        if self.lanes is not None:
            return self.lanes
        if callable(self._sequences):
            self._sequences = self._sequences()
        return self._sequences

    def positions_along(self, h) -> jax.Array:
        """The positions of h (..., s, e) for a layer that makes its own
        rotary angles: the lanes', or 0 .. s - 1."""
        return jnp.arange(h.shape[-2]) if self.lanes is None else self.lanes


class Mixer:
    """What a layer's mixer answers, built once in its class's `__init__`
    from the config's fields: `shapes(std, out_std)`, the `(shape, init
    std)` of its leaves of a layer; `pools`, what it keeps of a sequence;
    `decode_kernel(page_size, dtype)`, the name of its decode step's kernel
    ("einsum": its shapes do not tile and it gathers; None: it has none);
    and its three forwards on the layer's input `h`, each returning the
    mixer's output ready for the residual addition: `hidden` over whole
    sequences (b, s, e); `prefill` over one padded prompt, (s, e) or (1, s,
    e) as the class carries its stream, writing row `li` of its pools;
    `decode_step` over a batch of lanes (B, e), reading and writing row
    `li`. The last two return (output, the pools written). One that keeps
    pages says its `dtype`, the activations'."""

    closes = R.MIXER_OUT        # the region of its residual addition
    pools: Tuple[Pool, ...] = ()
    # whether `prefill` reads a batch of one through the flash kernel (a
    # class of such mixers alone carries its stream (1, s, e))
    batched = False
    chunk = 0                   # positions a prefill's scan takes at once
    # (cache key, names) of what its decode steps count, summed over its
    # layers, each an int32 scalar the engine's counters take by name
    counts: Optional[Tuple[str, Tuple[str, ...]]] = None

    def open(self, at: Walk) -> None:
        """Once a program, before its layers: what every layer of the
        mixer reads (rotary tables), into `at.tables`."""

    def walk_block_pages(self, page_size: int, max_pages: int) -> int:
        """Pages a block of its decode kernel's walk holds, asked what the
        kernel asks: one layer's page of all its pools."""
        return _paged.walk_block_pages(
            sum(pool.bytes(self.dtype, page_size) for pool in self.pools),
            page_size, max_pages)

    def page_run(self, page_size: int, max_pages: int,
                 fixed: int = 0) -> int:
        """Pages one copy of its decode walk would bring over tables of
        `max_pages` entries, the first `fixed` of the fixed class, from
        shapes alone (`PagedDecoder.page_run` decides; `decode_step` reads
        the decision off `Walk.run`, never this)."""
        return 1


@dataclasses.dataclass(frozen=True)
class Layer:
    """A row of a served class's table: the `mixers` that read the stream
    through the norm `norm` (None: as it is) and whose outputs are summed
    into it, then the feed-forward behind the norm `ffn` (None: none) with
    `experts` routed experts held here (0: dense). `rows` and `expert_row`
    are `PagedDecoder._lay`'s: the row of its pools each mixer reads and
    writes, the row of `"moe_load"`."""
    mixers: Tuple[Mixer, ...] = ()
    norm: Optional[str] = "attn_norm"
    ffn: Optional[str] = "mlp_norm"
    experts: int = 0
    rows: Tuple[int, ...] = ()
    expert_row: int = 0


class PagedDecoder:
    """A model as the serving engine sees it: fourteen asks.

    - `init_cache(num_pages, page_size, dtype=None, fixed_pages=0)`: the
      zeroed cache, `num_pages` pages in the pools that grow with a
      sequence and `fixed_pages` of the fixed class where there is one;
    - `prefill(params, tokens, true_len, page_table, cache, page_size)`:
      one padded prompt, as `models.decode.prefill`; returns
      (last-position logits (vocab,) f32, cache), the cache donated;
    - `decode_step(params, cache, tokens, positions, page_tables, active,
      page_size)`: a padded batch advanced by one token each, as
      `models.decode.decode_step`; (logits (B, vocab) f32, cache), donated;
    - `cache_page_bytes(page_size, tp_shards=1, dtype=None, fixed=False)`:
      bytes one page costs a shard, all layers (`fixed`: one of that class);
    - `decode_attention(page_size, dtype=None)`: which kernels a
      `decode_step` traced here holds, by name, or "einsum";
    - `walk_block_pages(page_size, max_pages)`: pages a block of the decode
      kernel's walk holds (the engine's walk counts stand on it);
    - `fixed_pages`, `page_run`, `table_pages`, `fixed_step_counts`,
      `prefill_counts`, `step_stats`, `cache_stats`, `pool_rows`.

    A class whose layers are held one by one lays its table in `__init__`
    (`_lay`: its mixers and a `Layer` a layer), says `layer_shapes(i)` (a
    tree of `(shape, init std)` of layer i's leaves, the mixers' from
    `Mixer.shapes`) and what is its own, and every ask is answered here by
    a walk over the table. `Transformer` (stacked layers, a mesh) keeps its
    own six."""

    # why the class refuses a mesh: what is not sharded over chips yet
    # (PERF.md section 7)
    no_mesh = ""
    # whether the head is the embedding's own table, read by its other
    # dimension (`logits = N_f(x) E^T`): there is then no `"lm_head"`
    tied_head = False
    # the counts a class with expert layers keeps (`ExpertCounts`)
    step_count_names: Tuple[str, ...] = ()
    # a prompt's pages by the count it holds (`prefill_page_ids_held`): a
    # ring needs that form, and `HybridDelta`'s text has always had it
    pages_by_count = False
    mixers: Tuple[Mixer, ...] = ()
    layers: Tuple[Layer, ...] = ()
    pools: Tuple[Tuple[Pool, int], ...] = ()    # (pool, its layers)

    def __init__(self, config, mesh=None):
        if mesh is not None:
            raise NotImplementedError(
                f"{type(self).__name__} runs on one device and takes no "
                f"mesh: {self.no_mesh}")
        self.config = config

    def _lay(self, mixers: Sequence[Mixer], rows: Sequence[Layer]) -> None:
        """The class's table: `mixers` in the order their kernels are
        named (those no row has are left out), `rows` a layer. A mixer's
        row of its pools is the count of earlier layers that keep pools of
        those names."""
        held: Dict[Tuple[str, ...], int] = {}
        layers, expert_rows = [], 0
        for row in rows:
            at = []
            for mixer in row.mixers:
                names = tuple(pool.name for pool in mixer.pools)
                at.append(held.get(names, 0))
                held[names] = at[-1] + 1
            layers.append(dataclasses.replace(
                row, rows=tuple(at), expert_row=expert_rows))
            expert_rows += bool(row.experts)
        self.layers = tuple(layers)
        self.mixers = tuple(m for m in mixers
                            if any(m in row.mixers for row in rows))
        pools = {pool.name: (pool, held[tuple(p.name for p in m.pools)])
                 for m in self.mixers for pool in m.pools}
        self.pools = tuple(pools.values())

    # ------------------------------------------------------------ init
    def param_count(self) -> int:
        c = self.config
        tables = 1 if self.tied_head else 2
        return (tables * c.vocab_size * c.d_model + c.d_model + sum(
            math.prod(shape) for i in range(c.n_layers)
            for shape, _ in jax.tree_util.tree_leaves(
                self.layer_shapes(i), is_leaf=_is_shape)))

    def init(self, key: jax.Array) -> Params:
        c = self.config
        pd = c.parameter_dtype
        keys = jax.random.split(key, c.n_layers + 1)
        top = {"embed": ((c.vocab_size, c.d_model), 0.02)}
        if not self.tied_head:
            top["lm_head"] = ((c.d_model, c.vocab_size), 0.02)
        top = fill(keys[-1], top, pd)
        return {**top, "final_norm": jnp.zeros((c.d_model,), pd),
                "layers": [fill(keys[i], self.layer_shapes(i), pd)
                           for i in range(c.n_layers)]}

    # --------------------------------------------------------- forward
    @R.region(R.EMBED)
    def _embed(self, params: Params, tokens):
        return params["embed"].astype(self.config.activation_dtype)[tokens]

    @R.region(R.NORM)
    def _norm(self, x, w):
        return rms_norm(x, w, self.config.norm_eps, None)

    @R.region(R.HEAD)
    def _final_norm(self, params: Params, x):
        """The stream's last norm: the head's, not a layer's."""
        return rms_norm(x, params["final_norm"], self.config.norm_eps, None)

    def apply(self, params: Params, tokens: jax.Array) -> jax.Array:
        """tokens (b, s) int32 -> logits (b, s, vocab) in f32."""
        x = self.hidden(params, tokens)
        with R.region(R.HEAD):
            return self._head(params, x)

    def _head(self, params: Params, x):
        """The normed stream x (..., e) through the head, in f32: `lm_head`,
        or the embedding's table contracted over its second dimension as it
        lies (no transposed copy is made)."""
        ad = self.config.activation_dtype
        if self.tied_head:
            return jnp.einsum("...e,ve->...v", x, params["embed"].astype(
                ad)).astype(jnp.float32)
        return (x @ params["lm_head"].astype(ad)).astype(jnp.float32)

    def loss(self, params: Params, batch: Dict[str, jax.Array]):
        """Causal LM loss of batch["tokens"] (b, s), as
        `Transformer.loss`, with no auxiliary term. A kernel that has no
        backward (the windowed flash forward, the recurrent layers' chunk
        kernels) runs its plain form in `hidden` (PERF.md section 7)."""
        tokens = batch["tokens"]
        mask = batch.get("loss_mask")
        logits = self.apply(params, tokens)
        with R.region(R.HEAD):
            logits = logits[:, :-1]
            if mask is not None:
                mask = mask[:, 1:]
            loss, _ = softmax_cross_entropy(logits, tokens[:, 1:],
                                            mask=mask)
            return loss

    @R.region(R.HEAD)
    def _logits(self, params: Params, x, true_len=None):
        """The tail of both served programs: the final norm of the stream
        x, of a prefill (`true_len`; x (s, e) or (1, s, e)) the prompt's
        last position alone, through the head, in f32."""
        x = self._final_norm(params, x)
        if true_len is not None:
            x = jnp.take(x[0] if x.ndim == 3 else x, true_len - 1, axis=0)
        return self._head(params, x)

    # -------------------------------------------------------- the walk
    def _add(self, row: Layer, layer: Params, x, outs):
        """The stream x and a row's mixers' outputs, summed."""
        for mixer, out in zip(row.mixers, outs):
            with R.region(mixer.closes):
                x = x + out
        return x

    def _block_ffn(self, layer: Params, x, valid=None,
                   norm: str = "mlp_norm"):
        """x (..., e) + ffn(norm(x)), the class's `_ffn(layer, tokens (T,
        e), valid)` behind the layer's norm `norm`; returns (x, expert
        counts or None)."""
        h = self._norm(x, layer[norm])
        y, counts = self._ffn(layer, h.reshape(-1, h.shape[-1]),
                              None if valid is None else valid.reshape(-1))
        with R.region(R.FFN):       # the residual addition
            return x + y.reshape(x.shape), counts

    def _row(self, row: Layer, layer: Params, x, mix, valid=None):
        """One row of the table on the stream x: `mix(mixer, layer, h, li)`
        is a mixer's forward of the program being traced on row `li` of its
        pools. Returns (x, the feed-forward's expert counts or None)."""
        if row.mixers:
            h = x if row.norm is None else self._norm(x, layer[row.norm])
            x = self._add(row, layer, x, [
                mix(mixer, layer, h, li)
                for mixer, li in zip(row.mixers, row.rows)])
        if row.ffn is None:
            return x, None
        return self._block_ffn(layer, x, valid, row.ffn)

    def _open(self, *given, **named) -> Walk:
        """A program's `Walk`, every mixer's tables made."""
        at = Walk(*given, **named)
        for mixer in self.mixers:
            mixer.open(at)
        return at

    def _pool_of(self, pools: Cache, kind: str):
        """The first pool of `kind` in a cache (None: the class has none):
        its second dimension counts the kind's pages or slots."""
        return next((pools[pool.name] for pool, _ in self.pools
                     if pool.kind == kind), None)

    def hidden(self, params: Params, tokens: jax.Array) -> jax.Array:
        """tokens (b, s) -> hidden states after the final norm."""
        b, s = tokens.shape
        x = self._embed(params, tokens)
        at = self._open(lambda: jnp.broadcast_to(jnp.arange(s), (b, s)))
        for row, layer in zip(self.layers, params["layers"]):
            x, _ = self._row(row, layer, x, lambda mixer, layer, h, li:
                             mixer.hidden(layer, h, at))
        return self._final_norm(params, x)

    def prefill(self, params: Params, tokens: jax.Array, true_len,
                page_table: jax.Array, cache: Cache,
                page_size: int) -> Tuple[jax.Array, Cache]:
        """Every mixer over the padded prompt, what it keeps written in
        place: whole pages (a ring's last ones), a slot whole, so that one
        reused holds nothing of its last owner. Padding past `true_len` is
        given to no expert."""
        pools = dict(cache)
        s = tokens.shape[0]
        batched = all(mixer.batched for mixer in self.mixers)
        x = self._embed(params, tokens)                         # (s, e)
        if batched:
            x = x[None]
        at = self._open(lambda: jnp.arange(s)[None], true_len=true_len)
        paged, ring = self._pool_of(pools, PAGED), self._pool_of(pools, RING)
        if ring is not None or self.pages_by_count:
            at.pages[PAGED], at.pages[RING] = prefill_page_ids_held(
                page_table, true_len, s, paged.shape[1], page_size,
                *(() if ring is None else (self.fixed_pages(page_size),
                                           ring.shape[1])))
        elif paged is not None:
            at.pages[PAGED] = prefill_page_ids(page_table, true_len, s,
                                               paged.shape[1], page_size)
        slots = self._pool_of(pools, SLOT)
        if slots is not None:
            at.slot = prefill_state_slot(page_table, slots.shape[1] - 1)
        valid = None
        if any(row.experts for row in self.layers):
            with R.region(R.CACHE):
                valid = jnp.arange(s) < true_len
                if batched:
                    valid = valid[None]

        def mix(mixer, layer, h, li):
            out, written = mixer.prefill(layer, h, pools, li, at)
            pools.update(written)
            return out

        for row, layer in zip(self.layers, params["layers"]):
            x, _ = self._row(row, layer, x, mix, valid)
        return self._logits(params, x, true_len), pools

    def decode_step(self, params: Params, cache: Cache, tokens: jax.Array,
                    positions: jax.Array, page_tables: jax.Array,
                    active: jax.Array,
                    page_size: int) -> Tuple[jax.Array, Cache]:
        """An inactive lane, or one whose table is unassigned, writes no
        page, no slot, counts nothing and is given to no expert."""
        pools = dict(cache)
        x = self._embed(params, tokens)                         # (B, e)
        at = self._open(lanes=positions)
        paged, ring = self._pool_of(pools, PAGED), self._pool_of(pools, RING)
        if paged is not None:
            entry, page, at.offset, at.lengths = lane_entries(
                positions, page_tables, active, paged.shape[1], page_size)
            at.pages[PAGED] = page, page_tables
            at.run = self.page_run(page_size, page_tables.shape[1])
            at.fixed = self.fixed_pages(page_size)
        if ring is not None:
            held = self.fixed_pages(page_size)
            with R.region(R.CACHE):
                tables = page_tables[:, :held]
                at.pages[RING] = lane_page(tables, entry % held, active,
                                           ring.shape[1]), tables
        slots = self._pool_of(pools, SLOT)
        if slots is not None:
            at.slot = decode_state_slots(page_tables, active,
                                         slots.shape[1] - 1)
        for mixer in self.mixers:
            if mixer.counts:
                key, names = mixer.counts
                pools[key] = dict.fromkeys(names, jnp.int32(0))
        if self.step_count_names:
            load, sums = pools["moe_load"], self._step_sums()

        def mix(mixer, layer, h, li):
            out, written = mixer.decode_step(layer, h, pools, li, at)
            pools.update(written)
            return out

        for row, layer in zip(self.layers, params["layers"]):
            x, counts = self._row(row, layer, x, mix, active)
            if counts is not None:
                with R.region(R.MOE_ROUTE):
                    load = load.at[row.expert_row].add(counts["load"])
                sums = self._count_step(sums, counts)
        if self.step_count_names:
            pools.update(self._counted(load, sums))
        return self._logits(params, x), pools

    # ------------------------------------------------ what an engine asks
    def init_cache(self, num_pages: int, page_size: int, dtype=None,
                   fixed_pages: int = 0) -> Cache:
        """`num_pages` pages in the pools that grow with a sequence;
        `fixed_pages` of the allocator's fixed class: a ring's pages, or
        state slots (one a sequence) and one more, nobody's."""
        dt = dtype or self.config.activation_dtype

        def zeros():
            cache = {pool.name: pool.zeros(layers, num_pages, page_size,
                                           fixed_pages, dt)
                     for pool, layers in self.pools}
            for mixer in self.mixers:
                if mixer.counts:
                    key, names = mixer.counts
                    cache[key] = {name: jnp.zeros((), jnp.int32)
                                  for name in names}
            if self.step_count_names:
                cache.update(self._zero_counts())
            return cache

        return jax.jit(zeros)()

    def _pool_bytes(self, kinds, dtype=None, page_size: int = 1,
                    tp_shards: int = 1, names=None) -> int:
        """Bytes of a page (a slot) of the pools of `kinds`, all their
        layers, on a shard; of those named `names` alone where given."""
        dt = dtype or self.config.activation_dtype
        return sum(layers * pool.bytes(dt, page_size, tp_shards)
                   for pool, layers in self.pools if pool.kind in kinds
                   and (names is None or pool.name in names))

    def cache_page_bytes(self, page_size: int, tp_shards: int = 1,
                         dtype=None, fixed: bool = False) -> int:
        """Bytes one page costs a shard, all layers: of the pools
        `num_pages` counts, rows as the pools hold them, padding and all;
        of the fixed class (`fixed`) a ring's page and what the layers
        that keep one size a sequence keep of it, which such a page costs
        besides."""
        if fixed:
            return self._pool_bytes((RING,), dtype, page_size,
                                    tp_shards) + self.state_bytes(dtype)
        return self._pool_bytes((PAGED,), dtype, page_size, tp_shards)

    def page_bytes(self, page_size: int, tp_shards: int = 1,
                   dtype=None) -> int:
        """Of the pools that grow with a sequence."""
        return self.cache_page_bytes(page_size, tp_shards, dtype)

    def state_bytes(self, dtype=None) -> int:
        """Bytes the layers that keep one size a sequence keep of it,
        whatever its length, as the pools hold them."""
        return self._pool_bytes((SLOT,), dtype)

    @property
    def pool_rows(self) -> Optional[int]:
        """Rows of a pool that holds one row an attention where that is
        not one a layer (the engine's `cache_init` span carries it); None:
        no such pool."""
        return next((layers for pool, layers in self.pools
                     if pool.an_attention), None)

    @property
    def expert_load_shape(self) -> Tuple[int, int]:
        """(expert layers, experts held in each)."""
        held = [row.experts for row in self.layers if row.experts]
        return len(held), max(held, default=0)

    def decode_attention(self, page_size: int, dtype=None) -> str:
        """The kernel of each mixer that has one, in the table's order of
        mixers, or "einsum" where one of them gathers."""
        dt = dtype or self.config.activation_dtype
        names = [mixer.decode_kernel(page_size, dt) for mixer in self.mixers]
        if "einsum" in names:
            return "einsum"
        return "+".join(dict.fromkeys(name for name in names if name))

    def walk_block_pages(self, page_size: int, max_pages: int,
                         fixed: bool = False) -> int:
        """Pages a block of the decode kernel's walk holds over tables of
        `max_pages`: of the first mixer that keeps pages, or (`fixed`) a
        ring."""
        kind = RING if fixed else PAGED
        return next(mixer for mixer in self.mixers if mixer.pools and (
            mixer.pools[0].kind == kind)).walk_block_pages(
                page_size, max_pages)

    def fixed_pages(self, page_size: int) -> int:
        """Pages of the allocator's fixed class a sequence holds for ever
        (0: `kv_cache.PageAllocator`'s one class): its ring, or one, its
        first table entry, which names its slot."""
        kinds = {pool.kind: pool for pool, _ in self.pools}
        if RING in kinds:
            return _paged.ring_pages(kinds[RING].window, page_size)
        return int(SLOT in kinds)

    def page_run(self, page_size: int, max_pages: int) -> int:
        """Pages of the class that grows that a sequence is to be handed
        at once, ids behind one another from a multiple of it on
        (`kv_cache.PageAllocator`'s `run`): what one copy of the class's
        decode walk brings, as its mixers answer. 1: a page at a time, in
        any order. A class that keeps a fixed page has its runs open at
        table entry `fixed_pages`, which the walks are told (`Walk.fixed`),
        and the same answer for a table of `max_pages` entries and for the
        one `table_pages` makes of it."""
        fixed = self.fixed_pages(page_size)
        return max((mixer.page_run(page_size, max_pages, fixed)
                    for mixer in self.mixers), default=1)

    def table_pages(self, page_size: int, pages: int) -> int:
        """Entries of a sequence's page table where a sequence holds up to
        `pages` pages: `pages`, or for a class that keeps a fixed page and
        asks for runs, the fixed entries and then whole runs
        (`ops.paged_attention.run_table_pages`). The one place the engine's
        tables and the step's walks take a table's width from."""
        return _paged.run_table_pages(pages, self.fixed_pages(page_size),
                                      self.page_run(page_size, pages))

    def fixed_step_counts(self, length: int, page_size: int,
                          kernel: bool = True) -> Dict[str, int]:
        """What a lane's fixed part costs a decode step, by the names the
        engine's `decode_dispatch` span carries: a lane's state slot, and
        the bytes the recurrent layers move for it (state and tail, read
        and written), whatever its `length`."""
        state = self.state_bytes()
        return {"state_slots": 1, "state_bytes": 2 * state} if state else {}

    def prefill_counts(self, tokens: int, bucket: int) -> Dict[str, int]:
        """What a prefill of `tokens` in its `bucket` adds to the engine's
        prefill span: the chunks a recurrent layer scans, those that hold
        the prompt (the chunk kernels skip the bucket's others)."""
        chunk = max((mixer.chunk for mixer in self.mixers), default=0)
        return {"scan_chunks": -(-tokens // chunk)} if chunk else {}

    def step_stats(self, cache: Cache) -> Dict[str, jax.Array]:
        """What the last decode step counted: scalars still on the device
        (the engine fetches them with the step's tokens), by the names its
        counters take."""
        return {name: n for mixer in self.mixers if mixer.counts
                for name, n in cache[mixer.counts[0]].items()}

    def cache_stats(self, cache: Cache) -> Dict[str, Any]:
        """For `EngineCore.device_stats()`."""
        return {}


# ------------------------------------------------- the cache's addresses
@R.region(R.CACHE)
def lane_page(page_tables, entry, active, oob: int):
    """The pool page each lane of a decode step writes: entry `entry` (B,)
    of its table; `oob` (dropped) for a lane that is inactive or whose
    entry is unassigned."""
    page = jnp.take_along_axis(page_tables, entry[:, None], axis=1)[:, 0]
    return jnp.where(active & (page >= 0), page, oob)


@R.region(R.CACHE)
def lane_entries(positions, page_tables, active, num_pages: int,
                 page_size: int):
    """Where each lane of a decode step writes and how far it sees:
    (entry, wr_page, wr_slot, lengths), `entry` the table entry the
    position lies on (a ring wraps it). A lane that is inactive or whose
    page is unassigned writes to page `num_pages`, which `mode="drop"`
    drops."""
    entry = positions // page_size
    wr_page = lane_page(page_tables, entry, active, num_pages)
    return entry, wr_page, positions % page_size, jnp.where(
        active, positions + 1, 0)


def decode_lanes(positions, page_tables, active, num_pages: int,
                 page_size: int):
    """`lane_entries`' (wr_page, wr_slot, lengths)."""
    return lane_entries(positions, page_tables, active, num_pages,
                        page_size)[1:]


@R.region(R.CACHE)
def prefill_page_ids(page_table, true_len, s: int, num_pages: int,
                     page_size: int):
    """The pages a padded prompt of `s` positions writes: the table's
    first ceil(s / page_size) entries, those wholly past `true_len`
    replaced by `num_pages` (dropped)."""
    n = -(-s // page_size)
    page_ids = jnp.take(page_table, jnp.arange(n), mode="clip")
    return jnp.where(jnp.arange(n) * page_size < true_len, page_ids,
                     num_pages)


@R.region(R.CACHE)
def prefill_page_ids_held(page_table, true_len, s: int, num_pages: int,
                          page_size: int, ring: int = 0,
                          ring_pages: int = 0):
    """`prefill_page_ids` by the count of pages the prompt fills (`held` =
    ceil(true_len / page_size): page j is written where `j < held`), and
    beside it the ids for a ring of `ring` pages in a pool of `ring_pages`
    (None without one): logical page j goes to the table's entry `j mod
    ring`, and only the newest page at each entry is written, the last
    `ring` of those held. Returns (page ids, ring ids)."""
    n = -(-s // page_size)
    j = jnp.arange(n)
    held = -(-true_len // page_size)
    ids = jnp.where(j < held, jnp.take(page_table, j, mode="clip"),
                    num_pages)
    if not ring:
        return ids, None
    return ids, jnp.where((j < held) & (j >= held - ring),
                          jnp.take(page_table, j % ring, mode="clip"),
                          ring_pages)


@R.region(R.CACHE)
def prefill_state_slot(page_table, slots: int):
    """The slot a prompt's state is written to: its table's first entry;
    past the pool (`slots + 1`: dropped) where that is no slot of the
    class."""
    slot = page_table[0]
    return jnp.where((slot >= 0) & (slot < slots), slot, slots + 1)


@R.region(R.CACHE)
def decode_state_slots(page_tables, active, slots: int):
    """The slot each lane of a decode step updates its state and its
    convolution's tail in: -1 (the step kernels leave both alone) for a
    lane that is inactive or whose first entry is no slot of the class."""
    first = page_tables[:, 0]
    return jnp.where(active & (first >= 0) & (first < slots), first, -1)


@R.region(R.MIXER_CORE)
def write_slot(pools: Cache, li: int, slot, state, tail) -> Cache:
    """A prefill's state and tail written whole into `slot` of row `li` of
    the pools `"state"` and `"tail"`, so that a slot reused holds nothing
    of its last owner (`state` None: the slot holds a tail alone, a
    convolution that is the whole mixer). Returns the pools written."""
    out = {} if state is None else {
        "state": pools["state"].at[li, slot].set(state, mode="drop")}
    out["tail"] = pools["tail"].at[li, slot].set(
        fold_tail(tail, pools["tail"].shape).astype(
            pools["tail"].dtype), mode="drop")
    return out


class ExpertCounts:
    """What a class with expert layers keeps of them in its cache:
    `"moe_load"` (`expert_load_shape`: expert layers, experts held) int32,
    pairs an expert since the cache was made,
    and `"moe_step"`, the last decode step's counts summed over the expert
    layers, each an int32 scalar, those of `moe.STEP_COUNTS` the class
    names (the engine's counters take their names from `step_stats`)."""

    step_count_names: Tuple[str, ...] = STEP_COUNTS

    def _zero_counts(self) -> Cache:
        """The cache's two entries, zeroed (inside `init_cache`'s jit)."""
        return {"moe_load": jnp.zeros(self.expert_load_shape, jnp.int32),
                "moe_step": {name: jnp.zeros((), jnp.int32)
                             for name in self.step_count_names}}

    def _step_sums(self):
        """A decode step's running sums before its first expert layer."""
        return [jnp.int32(0)] * len(self.step_count_names)

    @staticmethod
    @R.region(R.MOE_ROUTE)
    def _count_step(sums, counts):
        """One expert layer's `counts` (`dropless_moe_ffn`'s) added to a
        step's running sums."""
        return [a + n for a, n in zip(sums, step_counts(counts))]

    def _counted(self, load, sums) -> Cache:
        """The cache's two entries after a decode step."""
        return {"moe_load": load,
                "moe_step": dict(zip(self.step_count_names, sums))}

    def step_stats(self, cache: Cache) -> Dict[str, jax.Array]:
        return {**(cache["moe_step"] if self.expert_load_shape[0] else {}),
                **super().step_stats(cache)}

    def cache_stats(self, cache: Cache) -> Dict[str, Any]:
        """Pairs a held expert since the cache was made, by expert
        layer."""
        return {"moe_load": jax.device_get(cache["moe_load"]).tolist()}
