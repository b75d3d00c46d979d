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
pool, which `mode="drop"` discards. A model that keeps a state of fixed
size a sequence (`StateSlots`) keeps it at the slot the table's **first**
entry names, a page of the allocator's fixed class
(`serve/llm/kv_cache.py`); the pools have one slot more than the class,
nobody's, for what a kernel must put somewhere. `ExpertCounts` is what a
class with expert layers keeps of them in its cache.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import regions as R
from ray_tpu.models.moe import STEP_COUNTS, step_counts
from ray_tpu.ops.gated_delta import fold_tail
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


class PagedDecoder:
    """A model as the serving engine sees it: thirteen asks. Six a class
    answers itself:

    - `init_cache(num_pages, page_size, dtype=None[, fixed_pages=0])`: the
      zeroed cache, `num_pages` pages in the pools that grow with a
      sequence and `fixed_pages` of the fixed class where there is one;
    - `prefill(params, tokens, true_len, page_table, cache, page_size)`:
      one padded prompt, as `models.decode.prefill`; returns
      (last-position logits (vocab,) f32, cache), the cache donated;
    - `decode_step(params, cache, tokens, positions, page_tables, active,
      page_size)`: a padded batch advanced by one token each, as
      `models.decode.decode_step`; (logits (B, vocab) f32, cache), donated;
    - `cache_page_bytes(page_size, tp_shards=1, dtype=None[, fixed])`:
      bytes one page costs a shard, all layers (`fixed`: one of that class);
    - `decode_attention(page_size, dtype=None)`: which kernels a
      `decode_step` traced here holds, by name, or "einsum";
    - `walk_block_pages(page_size, max_pages)`: pages a block of the decode
      kernel's walk holds (the engine's walk counts stand on it).

    The other seven have the answer here of a model that keeps nothing of
    a sequence for ever, walks its pages one by one and counts nothing. A
    class whose layers are held one by one says `layer_shapes(i)` (a tree
    of `(shape, init std)` of layer i's leaves) and `hidden(params,
    tokens)` (the stream after the final norm) and gets `init`,
    `param_count`, `apply`, `loss` and the tail of both programs;
    `Transformer` (stacked layers, a mesh) keeps its own."""

    # why the class refuses a mesh: what is not sharded over chips yet
    # (PERF.md section 7)
    no_mesh = ""
    # whether the head is the embedding's own table, read by its other
    # dimension (`logits = N_f(x) E^T`): there is then no `"lm_head"`
    tied_head = False
    # rows of a pool that holds one row an attention where that is not one
    # a layer (the engine's `cache_init` span carries it); None: no such
    # pool
    pool_rows = None

    def __init__(self, config, mesh=None):
        if mesh is not None:
            raise NotImplementedError(
                f"{type(self).__name__} runs on one device and takes no "
                f"mesh: {self.no_mesh}")
        self.config = config

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

    # ------------------------------------------------ what an engine asks
    def fixed_pages(self, page_size: int) -> int:
        """Pages of the allocator's fixed class a sequence holds for ever
        (0: `kv_cache.PageAllocator`'s one class)."""
        return 0

    def page_run(self, page_size: int, max_pages: int) -> int:
        """Pages of the class that grows that a sequence is to be handed
        at once, ids behind one another from a multiple of it on
        (`kv_cache.PageAllocator`'s `run`): what one copy of the class's
        decode walk brings. 1: a page at a time, in any order."""
        return 1

    def fixed_step_counts(self, length: int, page_size: int,
                          kernel: bool = True) -> Dict[str, int]:
        """What a lane's fixed part costs a decode step, by the names the
        engine's `decode_dispatch` span carries."""
        return {}

    def prefill_counts(self, tokens: int, bucket: int) -> Dict[str, int]:
        """What a prefill of `tokens` in its `bucket` adds to the engine's
        prefill span."""
        return {}

    def step_stats(self, cache: Cache) -> Dict[str, jax.Array]:
        """What the last decode step counted: scalars still on the device
        (the engine fetches them with the step's tokens), by the names its
        counters take."""
        return {}

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
def decode_lanes(positions, page_tables, active, num_pages: int,
                 page_size: int):
    """Where each lane of a decode step writes and how far it sees:
    (wr_page, wr_slot, lengths). A lane that is inactive or whose page is
    unassigned writes to page `num_pages`, which `mode="drop"` drops."""
    wr_page = lane_page(page_tables, positions // page_size, active,
                        num_pages)
    return wr_page, positions % page_size, jnp.where(active, positions + 1,
                                                     0)


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


class StateSlots:
    """What a class whose layers keep something of one size a sequence
    answers the engine about it. The class says `state_bytes(dtype=None)`
    (bytes those layers keep of one sequence, whatever its length),
    `page_bytes(page_size, tp_shards=1, dtype=None)` (of the pools that
    grow with a sequence) and, where a prefill scans a recurrence, its
    config the `chunk` it scans by. Its pools `"state"` (a recurrence's)
    and `"tail"` (the rows a causal convolution continues from,
    `ops.gated_delta.tail_shape` a slot) are `(layers, slots + 1, ...)`; a
    slot may hold a tail alone (a convolution that is the whole mixer:
    there is then no `"state"` pool, and nothing scans)."""

    def fixed_pages(self, page_size: int) -> int:
        """One: a sequence's first table entry, which names its slot."""
        return int(self.state_bytes() > 0)

    def fixed_step_counts(self, length: int, page_size: int,
                          kernel: bool = True) -> Dict[str, int]:
        """A lane's state slot, and the bytes the recurrent layers move
        for it (state and tail, read and written), whatever its
        `length`."""
        return {"state_slots": 1, "state_bytes": 2 * self.state_bytes()}

    def prefill_counts(self, tokens: int, bucket: int) -> Dict[str, int]:
        """The chunks a recurrent layer scans: those that hold the prompt
        (the chunk kernels skip the bucket's others); nothing where no
        layer scans (a config without a `chunk`)."""
        chunk = getattr(self.config, "chunk", 0)
        return {"scan_chunks": -(-tokens // chunk)} if chunk else {}

    def cache_page_bytes(self, page_size: int, tp_shards: int = 1,
                         dtype=None, fixed: bool = False) -> int:
        """Bytes one page costs: `page_bytes` for a page of the pool
        `num_pages` counts; what the recurrent layers keep of a sequence
        (`fixed`), which its fixed-class page costs besides."""
        if fixed:
            return self.state_bytes(dtype)
        return self.page_bytes(page_size, tp_shards, dtype)

    @staticmethod
    @R.region(R.MIXER_CORE)
    def _write_slot(pools: Cache, li: int, slot, state, tail) -> Cache:
        """A prefill's state and tail written whole into `slot` of pool
        row `li`, so that a slot reused holds nothing of its last owner
        (`state` None: the slot holds a tail alone). Returns the pools
        written."""
        out = {} if state is None else {
            "state": pools["state"].at[li, slot].set(state, mode="drop")}
        out["tail"] = pools["tail"].at[li, slot].set(
            fold_tail(tail, pools["tail"].shape).astype(
                pools["tail"].dtype), mode="drop")
        return out


class ExpertCounts:
    """What a class with expert layers keeps of them in its cache:
    `"moe_load"` (`expert_load_shape`, a property the class gives: expert
    layers, experts held) int32, pairs an expert since the cache was made,
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
        return cache["moe_step"] if self.expert_load_shape[0] else {}

    def cache_stats(self, cache: Cache) -> Dict[str, Any]:
        """Pairs a held expert since the cache was made, by expert
        layer."""
        return {"moe_load": jax.device_get(cache["moe_load"]).tolist()}
