"""The paged decode-attention kernel against the gather + einsum it
replaces, through the Pallas interpreter on a bf16 pool; the path
predicate; and that the engine's compiled decode and prefill programs
update the pool in place (no copy of the pool's shape, the pool aliased).
CPU, in-process. The same kernel compiled for a v5e is in
`test_kernel_names_aot.py`; on the chip, in `chip_smoke.py`.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models.config import tiny
from ray_tpu.models.transformer import Transformer
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops.dispatch import compute_platform
from ray_tpu.serve.llm.engine import EngineCore

PAGE, HD, MAX_PAGES = 16, 128, 20
FULL = PAGE * MAX_PAGES


def _case(lengths, kvh=2, group=2, seed=0, layers=2, holes=(),
          MAX_PAGES=MAX_PAGES, HD=HD):
    """Random bf16 pools and queries; each lane's table lists pages drawn
    without order from a pool larger than all tables, -1 past the lane's
    pages and at `holes` (lane, table index)."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    pages = B * MAX_PAGES + 7
    shape = (layers, pages, PAGE, kvh * HD)
    k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, kvh * group, HD)), jnp.bfloat16)
    # page 0 is in no table: it is what a clamped -1 would name
    pt = (1 + rng.permutation(pages - 1))[:B * MAX_PAGES].reshape(
        B, MAX_PAGES).astype(np.int32)
    for b, n in enumerate(lengths):
        pt[b, -(-n // PAGE):] = -1
    for b, i in holes:
        pt[b, i] = -1
    return q, k, v, jnp.asarray(pt), jnp.asarray(lengths, jnp.int32)


def _close(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    # bf16 outputs of unit-variance values: a rounding step or two
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("lengths", [
    [1], [PAGE], [PAGE + 1], [FULL],
    [1, PAGE, PAGE + 1, FULL],                  # ragged lanes
    [8 * PAGE, 8 * PAGE + 1, 16 * PAGE, 3],     # the kernel block's edges
    [0, 45, 0, 129],                            # inactive lanes
    [0, 0],
], ids=lambda v: "-".join(map(str, v)))
def test_kernel_matches_einsum(lengths):
    q, k, v, pt, ln = _case(lengths)
    for layer in (0, 1):
        want = pa.paged_attention_reference(q, k, v, layer, pt, ln)
        got = pa.paged_decode_attention_kernel(q, k, v, layer, pt, ln)
        _close(got, want)
    idle = np.asarray(ln) == 0
    assert not np.asarray(got, np.float32)[idle].any()


@pytest.mark.parametrize("kvh,group", [(4, 1), (2, 2), (1, 4), (8, 2)])
def test_kernel_groups_query_heads(kvh, group):
    q, k, v, pt, ln = _case([37, 200, FULL], kvh=kvh, group=group, seed=1)
    _close(pa.paged_decode_attention_kernel(q, k, v, 1, pt, ln),
           pa.paged_attention_reference(q, k, v, 1, pt, ln))


# A head narrower than a 128-lane (`pa.LANE`): the heads of a lane are
# multiplied as one head of 128 under all their groups' query rows, each
# row zero outside its own head's part, and of a row's output its own part
# is kept. The gathered reference knows nothing of lanes.
@pytest.mark.parametrize("hd,kvh,group", [
    (64, 2, 4),         # a group of 4, one pair of kv heads: 8 rows a lane
    (64, 8, 4),         # the published 32 heads over 8
    (64, 4, 1),         # one query head a kv head
    (32, 4, 2),         # four heads a lane
    (128, 2, 4),        # heads of 128 as they were
])
@pytest.mark.parametrize("lengths", [
    [1, PAGE, PAGE + 1, FULL],                  # ragged lanes
    [8 * PAGE, 8 * PAGE + 1, 0, 3],     # a block's edges, an inactive lane
], ids=lambda v: "-".join(map(str, v)))
def test_kernel_takes_heads_that_share_a_lane(hd, kvh, group, lengths):
    q, k, v, pt, ln = _case(lengths, kvh=kvh, group=group, seed=3, HD=hd)
    assert q.shape == (len(lengths), kvh * group, hd)
    want = pa.paged_attention_reference(q, k, v, 1, pt, ln)
    got = pa.paged_decode_attention_kernel(q, k, v, 1, pt, ln)
    _close(got, want)
    idle = np.asarray(ln) == 0
    assert not np.asarray(got, np.float32)[idle].any()


def test_lane_queries_are_zero_outside_their_own_heads_part():
    q = jnp.arange(1, 1 + 2 * 8 * 64, dtype=jnp.float32).reshape(2, 8, 64)
    packed = pa._lane_queries(q, 4)         # 4 kv heads of 64, a group of 2
    assert packed.shape == (2, 2, 4, 128)   # 2 lanes of 2 heads x 2 rows
    rows = np.asarray(packed)
    # lane 0: heads 0, 1 (query rows 0-1 and 2-3)
    np.testing.assert_array_equal(rows[:, 0, :2, :64], np.asarray(q[:, 0:2]))
    np.testing.assert_array_equal(rows[:, 0, 2:, 64:], np.asarray(q[:, 2:4]))
    assert not rows[:, :, :2, 64:].any() and not rows[:, :, 2:, :64].any()
    # and `_own_parts` of an output laid out the same way undoes it
    np.testing.assert_array_equal(pa._own_parts(packed, 8, 64), q)
    # heads of whole lanes pass through as the group's rows
    wide = jnp.ones((2, 8, 128))
    assert pa._lane_queries(wide, 4).shape == (2, 4, 2, 128)


def test_window_kernel_takes_heads_that_share_a_lane():
    rng = np.random.default_rng(5)
    B, kvh, group, hd, page, ring, window = 3, 2, 4, 64, 16, 5, 48
    pool = (2, B * ring + 3, page, kvh * hd)
    k = jnp.asarray(rng.standard_normal(pool), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(pool), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, kvh * group, hd)), jnp.bfloat16)
    tables = jnp.asarray(1 + rng.permutation(B * ring).reshape(B, ring),
                         jnp.int32)
    lengths = jnp.asarray([70, 17, 0], jnp.int32)
    _close(pa.paged_window_decode_attention_kernel(q, k, v, 1, tables,
                                                   lengths, window),
           pa.paged_window_attention_reference(q, k, v, 1, tables, lengths,
                                               window))


def test_an_odd_count_of_half_lane_heads_is_refused():
    """Three kv heads of 64 are a row of 192 numbers, no whole lanes: the
    kernel refuses them, and the dispatching entry gathers."""
    q, k, v, pt, ln = _case([5, 40], kvh=3, group=2, HD=64)
    assert not pa.paged_decode_tiles(64, PAGE, jnp.bfloat16, 3 * 64)
    with pytest.raises(ValueError, match="does not tile 3 kv heads of 64"):
        pa.paged_decode_attention_kernel(q, k, v, 0, pt, ln)
    with compute_platform("tpu"):
        assert not pa.uses_kernel(64, PAGE, jnp.bfloat16, 3 * 64)
        assert pa.uses_kernel(64, PAGE, jnp.bfloat16, 4 * 64)


def test_kernel_skips_unassigned_entries():
    """A -1 inside a lane's pages is not read and not seen, whatever the
    page the clamped index would have named holds."""
    q, k, v, pt, ln = _case([100, FULL, 40], seed=2,
                            holes=[(0, 2), (1, 0), (1, 9), (2, 2)])
    want = pa.paged_attention_reference(q, k, v, 0, pt, ln)
    got = pa.paged_decode_attention_kernel(q, k, v, 0, pt, ln)
    _close(got, want)
    # page 0 is what a clamped -1 names: poison it, nothing may change
    poisoned = k.at[:, 0].set(jnp.nan), v.at[:, 0].set(jnp.nan)
    assert 0 not in np.asarray(pt)
    again = pa.paged_decode_attention_kernel(q, *poisoned, 0, pt, ln)
    np.testing.assert_array_equal(np.asarray(again, np.float32),
                                  np.asarray(got, np.float32))


def test_kernel_reads_pages_in_table_order():
    """The same keys under another placement of the pages give the same
    output: the table, not the pool's order, says where a position is."""
    q, k, v, pt, ln = _case([150, 60], seed=3)
    perm = np.random.default_rng(4).permutation(k.shape[1])
    inv = np.argsort(perm).astype(np.int32)
    moved = jnp.where(pt >= 0, jnp.asarray(inv)[jnp.maximum(pt, 0)], -1)
    got = pa.paged_decode_attention_kernel(q, k, v, 1, pt, ln)
    again = pa.paged_decode_attention_kernel(
        q, k[:, perm], v[:, perm], 1, moved.astype(jnp.int32), ln)
    np.testing.assert_array_equal(np.asarray(again, np.float32),
                                  np.asarray(got, np.float32))


# ------------------------------------------- the walk's blocks and pieces
# (`walk_budget`, of conftest.py, sets `WALK_BUFFER_BYTES` for a test)
LONG = 72           # pages a table: two blocks of 32 pages and a piece
BLOCK = 32 * PAGE   # positions of such a block; a piece holds 8 * PAGE


def _blocks_of_32(walk_budget, kvh=2):
    walk_budget(pa.BLOCK_SLOTS * 32 * 2 * PAGE * kvh * HD * 2)
    assert pa.walk_block_pages(2 * PAGE * kvh * HD * 2, PAGE, LONG) == 32
    assert pa.walk_prefixes(32, PAGE) == (8, 16, 32)


@pytest.mark.parametrize("lengths", [
    [BLOCK - 1, BLOCK, BLOCK + 1],              # a block's edges
    [2 * BLOCK - 1, 2 * BLOCK, 2 * BLOCK + 1],
    [8 * PAGE - 1, 8 * PAGE, 8 * PAGE + 1],     # a piece's, in the first
    [BLOCK + 8 * PAGE - 1, BLOCK + 8 * PAGE,    # and in a later block
     BLOCK + 8 * PAGE + 1],
    [16 * PAGE, 16 * PAGE + 1, 24 * PAGE + 1],  # two pieces, then whole
    [PAGE, 1, 0],                               # a page, a position, none
    [LONG * PAGE, 0, 3, BLOCK + 5],             # unlike lanes in one call
], ids=lambda v: "-".join(map(str, v)))
def test_kernel_walks_blocks_in_pieces(walk_budget, lengths):
    """Blocks of 32 pages multiplied in one or two pieces of 8 or whole:
    every edge of a block and of a piece gives what the gather gives."""
    _blocks_of_32(walk_budget)
    q, k, v, pt, ln = _case(lengths, seed=5, MAX_PAGES=LONG)
    _close(pa.paged_decode_attention_kernel(q, k, v, 1, pt, ln),
           pa.paged_attention_reference(q, k, v, 1, pt, ln))


@pytest.mark.parametrize("kvh,group", [(2, 1), (1, 2), (1, 6)])
def test_kernel_walks_blocks_at_each_group(walk_budget, kvh, group):
    _blocks_of_32(walk_budget, kvh)
    q, k, v, pt, ln = _case([2 * BLOCK + 40, BLOCK, 130], kvh=kvh,
                            group=group, seed=6, MAX_PAGES=LONG)
    _close(pa.paged_decode_attention_kernel(q, k, v, 0, pt, ln),
           pa.paged_attention_reference(q, k, v, 0, pt, ln))


def test_kernel_skips_holes_in_a_full_block_and_in_a_tail_piece(
        walk_budget):
    """-1 inside a block that is multiplied whole, inside the piece the
    last block is multiplied at, and as a lane's first page: not read, not
    seen; and what lies behind the tail's prefix is never multiplied,
    whatever an earlier lane left in the buffer."""
    _blocks_of_32(walk_budget)
    q, k, v, pt, ln = _case(
        [LONG * PAGE, BLOCK + 3 * PAGE, 2 * BLOCK + 20], seed=7,
        MAX_PAGES=LONG,
        holes=[(0, 0), (0, 17), (0, 31), (0, 40), (1, 5), (1, 33),
               (2, 64), (2, 65)])
    want = pa.paged_attention_reference(q, k, v, 0, pt, ln)
    got = pa.paged_decode_attention_kernel(q, k, v, 0, pt, ln)
    _close(got, want)
    poisoned = k.at[:, 0].set(jnp.nan), v.at[:, 0].set(jnp.nan)
    assert 0 not in np.asarray(pt)
    again = pa.paged_decode_attention_kernel(q, *poisoned, 0, pt, ln)
    np.testing.assert_array_equal(np.asarray(again, np.float32),
                                  np.asarray(got, np.float32))


# ------------------------------------- a lane starts the next lane's block
@pytest.fixture
def walk_spy(monkeypatch):
    """Counts, a grid step (a lane), the page copies the walk starts and
    waits for: `spy()` gives (started, waited) by lane since the last
    call. The kernel runs through the interpreter, so a callback can say
    when each copy's `start` and `wait` ran."""
    from jax.experimental import pallas as pl
    calls = (pa._paged_decode_call, pa._paged_window_decode_call,
             pa._mla_paged_decode_call)
    lane, log = [], []
    real_id, real_copy = pl.program_id, pa.pltpu.make_async_copy

    def program_id(axis):           # the walk asks once, at its top
        lane[:] = [real_id(axis)]
        return lane[0]

    class Copy:
        def __init__(self, *args):
            self.copy = real_copy(*args)

        def start(self):
            jax.debug.callback(lambda b: log.append((int(b), 0)), lane[0])
            self.copy.start()

        def wait(self):
            jax.debug.callback(lambda b: log.append((int(b), 1)), lane[0])
            self.copy.wait()

    monkeypatch.setattr(pa.pl, "program_id", program_id)
    monkeypatch.setattr(pa.pltpu, "make_async_copy", Copy)
    for call in calls:
        call.clear_cache()

    def spy(lanes):
        jax.effects_barrier()
        counts = np.zeros((lanes, 2), int)
        for b, what in log:
            counts[b, what] += 1
        del log[:]
        return counts[:, 0], counts[:, 1]
    yield spy
    for call in calls:
        call.clear_cache()


def _handed_on(started, waited):
    """The lanes that found their first block on its way: copies were
    started and not yet waited for when the lane's turn came. Every copy
    started is waited for in the call, a lane's by that lane."""
    ahead = np.cumsum(started - waited) - (started - waited)
    assert (ahead >= 0).all() and started.sum() == waited.sum()
    return [int(b) for b in np.flatnonzero((ahead > 0) & (waited > 0))]


def _pages(lengths, page=PAGE):
    return [-(-n // page) for n in lengths]


@pytest.mark.parametrize("lengths", [
    [0, 45, 0, 0, 129],                         # past empty lanes
    [77],                                       # a lane alone
    [0, 0, 0],                                  # nothing to walk
    [200, 0, 0],                                # nobody behind to hand to
    [FULL, 1, FULL],
], ids=lambda v: "-".join(map(str, v)))
def test_a_lane_starts_the_next_lanes_first_block(walk_spy, lengths):
    """The kernel's copies, counted a lane: every lane that holds pages
    but the first such finds its first block started, by the lane before
    it that holds pages; what `walk_first_blocks_hidden` says."""
    q, k, v, pt, ln = _case(lengths, seed=8)
    got = pa.paged_decode_attention_kernel(q, k, v, 1, pt, ln)
    _close(got, pa.paged_attention_reference(q, k, v, 1, pt, ln))
    started, waited = walk_spy(len(lengths))
    pages = np.asarray(_pages(lengths))
    np.testing.assert_array_equal(waited, 2 * pages)    # k and v
    full = [b for b, n in enumerate(pages) if n]
    assert _handed_on(started, waited) == full[1:]
    assert len(full[1:]) == pa.walk_first_blocks_hidden(pages)
    # a lane starts its own blocks but its first, and the first of the
    # lane behind it (here every lane is one block)
    for b, behind in zip(full, full[1:] + [None]):
        own = 2 * pages[b] if b == full[0] else 0
        assert started[b] == own + (2 * pages[behind] if behind else 0)


@pytest.mark.parametrize("blocks", [
    (1, 1, 1, 1),           # odd: the slot a lane begins at alternates
    (2, 2, 2),              # even: every lane begins at the same slot
    (1, 2, 3, 1, 2),        # mixed
    (3, 0, 1, 0, 2, 2, 1),
], ids=lambda v: "-".join(map(str, v)))
def test_the_slot_is_carried_from_lane_to_lane(walk_budget, walk_spy,
                                               blocks):
    """Lanes of one, two and three blocks of 32 pages behind one another:
    a lane's first block lands in the slot the lane before it is not
    multiplying from, whatever slot that lane stopped in."""
    lengths = [n * BLOCK - 40 if n else 0 for n in blocks]
    q, k, v, pt, ln = _case(lengths, seed=9, MAX_PAGES=3 * 32)
    # (the budget after the case: both clear the jitted calls' caches)
    _blocks_of_32(walk_budget)
    assert [pa.walk_counts(n, 32, PAGE)[0] for n in _pages(lengths)] == list(
        blocks)
    _close(pa.paged_decode_attention_kernel(q, k, v, 0, pt, ln),
           pa.paged_attention_reference(q, k, v, 0, pt, ln))
    handed = _handed_on(*walk_spy(len(lengths)))
    assert handed == [b for b, n in enumerate(blocks) if n][1:]
    assert len(handed) == pa.walk_first_blocks_hidden(_pages(lengths))


def test_holes_in_the_block_handed_on_and_in_the_block_that_hands(
        walk_budget, walk_spy):
    """-1 in the first block of a lane whose block was started for it
    (not copied by the lane before, not waited for by the lane itself) and
    in the last block of the lane that started it."""
    _blocks_of_32(walk_budget)
    lengths = [BLOCK + 5 * PAGE, 20 * PAGE, 2 * BLOCK]
    holes = [(0, 33), (0, 36), (1, 0), (1, 7), (1, 19), (2, 31), (2, 63)]
    q, k, v, pt, ln = _case(lengths, seed=10, MAX_PAGES=LONG, holes=holes)
    want = pa.paged_attention_reference(q, k, v, 0, pt, ln)
    got = pa.paged_decode_attention_kernel(q, k, v, 0, pt, ln)
    _close(got, want)
    started, waited = walk_spy(3)
    held = np.asarray(_pages(lengths)) - [2, 3, 2]
    np.testing.assert_array_equal(waited, 2 * held)
    assert _handed_on(started, waited) == [1, 2]
    # page 0 is what a clamped -1 names: poison it, nothing may change
    poisoned = k.at[:, 0].set(jnp.nan), v.at[:, 0].set(jnp.nan)
    again = pa.paged_decode_attention_kernel(q, *poisoned, 0, pt, ln)
    np.testing.assert_array_equal(np.asarray(again, np.float32),
                                  np.asarray(got, np.float32))


@pytest.mark.parametrize("lengths,holes", [
    # each lane is multiplied further into its slot than any before it
    ([3 * PAGE, 5 * PAGE, 12 * PAGE, 14 * PAGE, 20 * PAGE, 30 * PAGE],
     [(2, 11), (3, 13), (4, 19), (5, 29)]),
    # a long lane, then short ones handed the slots it filled, with holes
    ([2 * BLOCK + 20 * PAGE, 3 * PAGE, 12 * PAGE, 3 * PAGE, 30 * PAGE],
     [(1, 1), (2, 0), (2, 11), (4, 17)]),
], ids=["rising", "falling"])
def test_a_block_started_ahead_is_made_finite_to_its_own_lanes_reach(
        walk_budget, lengths, holes):
    """The interpreter's scratch begins as NaN, as a chip's may: a slot is
    made numbers as far as the block that lands in it will be multiplied,
    which for a block started ahead is what the NEXT lane reaches, not
    what the lane that starts it does (`_close` refuses a NaN)."""
    _blocks_of_32(walk_budget)
    q, k, v, pt, ln = _case(lengths, seed=11, MAX_PAGES=LONG, holes=holes)
    _close(pa.paged_decode_attention_kernel(q, k, v, 1, pt, ln),
           pa.paged_attention_reference(q, k, v, 1, pt, ln))


def _with_holes(tables, holes):
    tables = np.array(tables)
    for b, i in holes:
        tables[b, i] = -1
    return jnp.asarray(tables)


@pytest.mark.parametrize("block,lengths,holes", [
    (33, [1000, 0, 513, 40, 0, 2000], [(2, 5), (5, 32)]),   # a block each
    (16, [1000, 0, 513, 40, 0, 2000], [(0, 3), (3, 0)]),    # 3, 3 and 1
    (16, [100, 200, 300, 4000], []),                        # 1, 1, 2, 3
], ids=["one-block", "blocks-of-16", "rising"])
def test_window_lanes_hand_their_walk_on_over_a_ring(walk_budget, walk_spy,
                                                     block, lengths, holes):
    """The same over a ring: the next lane's first block begins at the
    first page ITS window reaches, its table wrapped at its own length."""
    from test_gqa_window_moe import _ring_case
    window, kvh = 512, 2
    q, kp, vp, tables, _ = _ring_case(lengths, window, PAGE, 4, kvh, seed=12)
    page_bytes = 2 * PAGE * kvh * HD * 4
    if block != 33:
        walk_budget(pa.BLOCK_SLOTS * block * page_bytes)
    assert pa.walk_block_pages(page_bytes, PAGE, 33) == block
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), 1,
            _with_holes(tables, holes), jnp.asarray(lengths, jnp.int32),
            window)
    got = pa.paged_window_decode_attention_kernel(*args)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(
        got, pa.paged_window_attention_reference(*args), rtol=2e-4,
        atol=2e-5)
    started, waited = walk_spy(len(lengths))
    read = [pa.ring_walk(n, window, PAGE)[1] // PAGE for n in lengths]
    assert waited.sum() == 2 * (sum(read) - len(holes))
    handed = _handed_on(started, waited)
    assert handed == [b for b, n in enumerate(lengths) if n][1:]
    assert len(handed) == pa.walk_first_blocks_hidden(read)


@pytest.mark.parametrize("lengths,holes", [
    ([0, 300, 0, 0, 40], []),
    ([600, 40, 300, 385], [(0, 70), (1, 0), (2, 3)]),   # 2 blocks, 1, 1, 1
    ([20, 129, 255, 513], [(1, 16), (2, 30), (3, 40)]),  # further each lane
], ids=["past-empty-lanes", "mixed-blocks", "rising"])
def test_latent_lanes_hand_their_walk_on(walk_budget, walk_spy, lengths,
                                         holes):
    """The latent kernel stands on the same walk: one pool's copies."""
    from test_mla_moe import _latent_case
    q, pool, tables, ln = _latent_case(lengths, seed=13, pages=330,
                                       max_pages=80)
    walk_budget(pa.BLOCK_SLOTS * 64 * 8 * 256 * 4)
    assert pa.walk_block_pages(8 * 256 * 4, 8, 80) == 64
    args = (q, pool, 1, _with_holes(tables, holes), ln, 128, 0.1)
    got = pa.mla_paged_decode_attention_kernel(*args)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, pa.mla_paged_attention_reference(*args),
                               atol=2e-5, rtol=2e-5)
    started, waited = walk_spy(len(lengths))
    pages = _pages(lengths, 8)
    assert waited.sum() == sum(pages) - len(holes)
    handed = _handed_on(started, waited)
    assert handed == [b for b, n in enumerate(lengths) if n][1:]
    assert len(handed) == pa.walk_first_blocks_hidden(pages)


# ------------------------------------------- a run of pages a copy (PR 64)
LPAGE, LWIDTH, LLATENT = 8, 256, 128
AHEAD = 1e30        # what the pages held ahead of a lane's length hold


def _latent_runs_case(lengths, run, max_pages=48, seed=0, heads=5,
                      aligned=True):
    """A float32 latent pool whose tables are laid as the allocator lays
    them at `run`: a lane holds whole runs, each `run` ids behind one
    another from a multiple of `run` on, the runs anywhere in the pool;
    the pages of a lane's last run that no position has reached hold
    `AHEAD`. Not `aligned`: a page at a time, anywhere (`run` 1 alone)."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    pages = B * max_pages + 2 * run
    pool = rng.normal(size=(2, pages, LPAGE, LWIDTH)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(B, heads, LWIDTH)), jnp.float32)
    tables = np.full((B, max_pages), -1, np.int32)
    free = (list(rng.permutation(pages // run) * run) if aligned
            else list(rng.permutation(pages)))
    for b, n in enumerate(lengths):
        live = -(-n // LPAGE)
        for k in range(-(-live // run) if aligned else live):
            first = free.pop()
            tables[b, k * run:(k + 1) * run] = first + np.arange(run)
        ahead = tables[b, live:][tables[b, live:] >= 0]
        pool[:, ahead] = AHEAD
    return q, jnp.asarray(pool), jnp.asarray(tables), jnp.asarray(
        lengths, jnp.int32)


def _latent_blocks_of_16(walk_budget, max_pages=48):
    walk_budget(pa.BLOCK_SLOTS * 16 * LPAGE * LWIDTH * 4)
    assert pa.walk_block_pages(LPAGE * LWIDTH * 4, LPAGE, max_pages) == 16


RUN_LANES = {
    "ends-inside-a-run": (5 * LPAGE, 9 * LPAGE + 3, 13 * LPAGE + 1),
    "one-page": (LPAGE, 1, 5),
    "an-empty-lane-between": (100, 0, 0, 60),       # the hand-on
    # blocks of 16 pages: 17 pages, 38 (three blocks), a block to the page
    "crosses-a-block": (16 * LPAGE + 1, 300, 16 * LPAGE),
}


@pytest.mark.parametrize("run,lanes,aligned", [
    *((run, lanes, True) for run in (1, 2, 4) for lanes in RUN_LANES),
    (1, "crosses-a-block", False),      # any table, as before PR 64
], ids=lambda v: str(v))
def test_latent_kernel_copies_a_run_of_pages_a_descriptor(
        walk_budget, walk_spy, run, lanes, aligned):
    """`run` pages a copy over tables laid in aligned runs give what the
    gather gives, what is held ahead of a lane's length never read into
    the output; the copies are one a run that holds a live page, and a
    lane still finds its first block started by the lane before it."""
    lengths = RUN_LANES[lanes]
    case = _latent_runs_case(lengths, run, seed=17, aligned=aligned)
    _latent_blocks_of_16(walk_budget)
    got = pa.mla_paged_decode_attention_kernel(*case[:2], 1, *case[2:],
                                               LLATENT, 0.1, run=run)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got)).max() < 1e3      # nothing of AHEAD
    np.testing.assert_allclose(got, pa.mla_paged_attention_reference(
        *case[:2], 1, *case[2:], LLATENT, 0.1), atol=2e-5, rtol=2e-5)
    started, waited = walk_spy(len(lengths))
    pages = _pages(lengths, LPAGE)
    assert waited.sum() == sum(-(-n // run) for n in pages)
    handed = _handed_on(started, waited)
    assert handed == [b for b, n in enumerate(lengths) if n][1:]
    assert len(handed) == pa.walk_first_blocks_hidden(pages)


def test_a_run_whose_first_entry_is_unassigned_is_a_hole(walk_budget):
    """A run is held where its first entry is: -1 there and the run's
    pages are not copied and not seen, in a full block and in a tail."""
    lengths = (300, 70)
    q, pool, tables, ln = _latent_runs_case(lengths, 2, seed=19)
    _latent_blocks_of_16(walk_budget)
    holes = np.asarray(tables).copy()
    holes[0, 4:6] = holes[0, 36:38] = holes[1, 0:2] = -1
    holes = jnp.asarray(holes)
    got = pa.mla_paged_decode_attention_kernel(q, pool, 0, holes, ln,
                                               LLATENT, 0.1, run=2)
    np.testing.assert_allclose(got, pa.mla_paged_attention_reference(
        q, pool, 0, holes, ln, LLATENT, 0.1), atol=2e-5, rtol=2e-5)


def test_a_table_that_ends_inside_a_block_is_walked_in_runs(walk_budget):
    """40 pages in blocks of 16: the last block is half a block, and whole
    runs of 4."""
    q, pool, tables, ln = _latent_runs_case((40 * LPAGE - 3, 33 * LPAGE), 4,
                                            max_pages=40, seed=23)
    _latent_blocks_of_16(walk_budget, 40)
    got = pa.mla_paged_decode_attention_kernel(q, pool, 1, tables, ln,
                                               LLATENT, 0.1, run=4)
    np.testing.assert_allclose(got, pa.mla_paged_attention_reference(
        q, pool, 1, tables, ln, LLATENT, 0.1), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("run,max_pages", [
    (4, 42),                # a table that is no whole runs
    (32, 64),               # a run longer than a block of 16
])
def test_a_walk_of_runs_refuses_tables_it_cannot_lay_runs_in(
        walk_budget, run, max_pages):
    case = _latent_runs_case((40, 40), 1, max_pages=max_pages)
    _latent_blocks_of_16(walk_budget, max_pages)
    with pytest.raises(ValueError, match="blocks of whole runs"):
        pa.mla_paged_decode_attention_kernel(*case[:2], 0, *case[2:],
                                             LLATENT, 0.1, run=run)


# ---------------------- runs behind a table's fixed entries (PR 66)
def _fixed_case(kernel, lengths, run, fixed, max_pages=48, seed=0,
                holes=()):
    """A float32 case of `kernel` ("latent": a pool of `LWIDTH` rows and 5
    heads; "heads": keys and values of 2 kv heads of 128 under 4 query
    heads, pages of `LPAGE`) whose tables are laid as the allocator lays
    those of a class that keeps `fixed` pages: a lane's first `fixed`
    entries ids of the fixed class (`0 .. lanes x fixed - 1`, in no order),
    then whole runs of `run` ids behind one another from a multiple of
    `run` on, anywhere behind that class; the table `run_table_pages`
    wide; what a lane holds ahead of its length holds `AHEAD`; `holes`
    (lane, entry) are unassigned, a run's first entry the whole run.
    Returns (q, pools, tables, lengths)."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    table = pa.run_table_pages(max_pages, fixed, run)
    first = -(-B * fixed // run) * run
    pages = first + B * (table - fixed) + 2 * run
    width, heads = (LWIDTH, 5) if kernel == "latent" else (2 * 128, 4)
    pools = [rng.normal(size=(2, pages, LPAGE, width)).astype(np.float32)
             for _ in range(1 if kernel == "latent" else 2)]
    q = jnp.asarray(rng.normal(size=(
        B, heads, LWIDTH if kernel == "latent" else 128)), jnp.float32)
    tables = np.full((B, table), -1, np.int32)
    singles = list(rng.permutation(B * fixed))
    free = list(first + rng.permutation((pages - first) // run) * run)
    for b, n in enumerate(lengths):
        live = -(-n // LPAGE)
        tables[b, :min(live, fixed)] = [
            singles.pop() for _ in range(min(live, fixed))]
        for k in range(-(-max(live - fixed, 0) // run)):
            at = fixed + k * run
            tables[b, at:at + run] = free.pop() + np.arange(run)
        ahead = tables[b, live:][tables[b, live:] >= 0]
        for pool in pools:
            pool[:, ahead] = AHEAD
    for b, e in holes:
        tables[b, e:e + (run if e >= fixed else 1)] = -1
    return q, tuple(jnp.asarray(pool) for pool in pools), jnp.asarray(
        tables), jnp.asarray(lengths, jnp.int32)


def _fixed_both_ways(kernel, case, layer, run, fixed):
    q, pools, tables, ln = case
    if kernel == "latent":
        return (pa.mla_paged_decode_attention_kernel(
            q, *pools, layer, tables, ln, LLATENT, 0.1, run=run,
            fixed=fixed), pa.mla_paged_attention_reference(
                q, *pools, layer, tables, ln, LLATENT, 0.1))
    return (pa.paged_decode_attention_kernel(
        q, *pools, layer, tables, ln, run=run, fixed=fixed),
        pa.paged_attention_reference(q, *pools, layer, tables, ln))


def _fixed_blocks_of_16(walk_budget, kernel):
    pools = 1 if kernel == "latent" else 2
    walk_budget(pa.BLOCK_SLOTS * 16 * pools * LPAGE * LWIDTH * 4)
    assert pa.walk_block_pages(pools * LPAGE * LWIDTH * 4, LPAGE, 48) == 16


@pytest.mark.parametrize("kernel,fixed,run", [
    (kernel, fixed, run) for kernel in ("latent", "heads")
    for fixed in (1, 34) for run in (1, 4, 8)])
def test_kernels_copy_runs_behind_a_tables_fixed_entries(
        walk_budget, walk_spy, kernel, fixed, run):
    """Both kernels that take `fixed` give what the gather gives over
    tables of `fixed` single pages, in no order, and whole runs of `run`
    behind them, in blocks of 16 pages: a lane that holds nothing, one
    inside its fixed entries, one that holds exactly those, one a run's
    first page alone, one that ends inside a run, one across three blocks
    and one at the table's end; nothing held ahead of a lane's length is
    read into the output; the copies are a page each of the fixed entries
    and one a run behind them, and a lane still finds its first block
    started by the lane before it."""
    table = pa.run_table_pages(48, fixed, run)
    lengths = (0, fixed * LPAGE - 3, fixed * LPAGE, fixed * LPAGE + 1,
               (fixed + run) * LPAGE + 3, 43 * LPAGE - 1, table * LPAGE)
    case = _fixed_case(kernel, lengths, run, fixed, seed=29)
    assert case[2].shape == (len(lengths), table)
    _fixed_blocks_of_16(walk_budget, kernel)
    got, want = _fixed_both_ways(kernel, case, 1, run, fixed)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got)).max() < 1e3      # nothing of AHEAD
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[0]).any()
    started, waited = walk_spy(len(lengths))
    pages = _pages(lengths, LPAGE)
    pools = 1 if kernel == "latent" else 2
    assert waited.sum() == pools * sum(
        min(n, fixed) + -(-max(n - fixed, 0) // run) for n in pages)
    handed = _handed_on(started, waited)
    assert handed == [b for b, n in enumerate(lengths) if n][1:]


@pytest.mark.parametrize("kernel,fixed,run", [
    ("latent", 1, 4), ("heads", 1, 8), ("latent", 34, 8), ("heads", 34, 4)])
def test_holes_among_fixed_entries_and_runs_are_not_read(
        walk_budget, kernel, fixed, run):
    """-1 at a fixed entry is that page's hole, at a run's first entry the
    run's: in a block multiplied whole and in a tail, not copied, not
    seen."""
    lengths = (48 * LPAGE - 5, (fixed + run) * LPAGE + 9, 20 * LPAGE)
    holes = [(0, 0), (0, fixed + run), (0, fixed + 3 * run),
             (1, fixed - 1), (2, fixed)]
    case = _fixed_case(kernel, lengths, run, fixed, seed=31, holes=holes)
    assert (np.asarray(case[2])[0, fixed + run:fixed + 2 * run] == -1).all()
    _fixed_blocks_of_16(walk_budget, kernel)
    got, want = _fixed_both_ways(kernel, case, 0, run, fixed)
    assert np.abs(np.asarray(got)).max() < 1e3
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_a_walk_behind_fixed_entries_refuses_a_table_of_no_whole_runs(
        walk_budget):
    case = _fixed_case("latent", (40, 40), 4, 1)
    _fixed_blocks_of_16(walk_budget, "latent")
    q, pools, tables, ln = case
    with pytest.raises(ValueError, match="blocks of whole runs"):
        pa.mla_paged_decode_attention_kernel(q, *pools, 0, tables, ln,
                                             LLATENT, 0.1, run=4, fixed=2)


# the kernels' texts as the parent (PR 65) traced them at these shapes (4
# lanes, tables of 64 pages of 16, float32; sha256 of `str(jaxpr)` without
# source lines): a walk told of no fixed entries, and one that walks a page
# a copy whatever it is told, is that program to the byte
KERNEL_TEXTS = {("latent", 1): "a4ded6dd67d81ee4",
                ("latent", 4): "99189648cbaa751f",
                ("heads", 1): "257e6db65feb88ae"}


@pytest.mark.parametrize("kernel,run,fixed", [
    *((kernel, run, 0) for kernel, run in sorted(KERNEL_TEXTS)),
    ("latent", 1, 1), ("latent", 1, 34), ("heads", 1, 1), ("heads", 1, 34)])
def test_a_walk_without_fixed_entries_traces_the_parents_text(kernel, run,
                                                              fixed):
    import hashlib
    import re
    S, i32, f32 = jax.ShapeDtypeStruct, jnp.int32, jnp.float32
    tables = (S((1,), i32), S((4, 64), i32), S((4,), i32))
    if kernel == "latent":
        traced = pa._mla_paged_decode_call.trace(
            S((4, 8, 256), f32), S((2, 300, 16, 256), f32), *tables,
            latent=128, sm_scale=0.1, interpret=True, run=run, fixed=fixed)
    else:
        pool = S((2, 300, 16, 256), f32)
        traced = pa._paged_decode_call.trace(
            S((4, 2, 4, 128), f32), pool, pool, *tables, interpret=True,
            sm_scale=0.1, run=run, fixed=fixed)
    text = re.sub(r" at (0x[0-9a-f]+|[^\s\]]+:\d+)", "", str(traced.jaxpr))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == KERNEL_TEXTS[
        kernel, run]


def test_tables_and_runs_behind_fixed_entries_are_sized_in_one_place():
    """`run_table_pages`, `run_pad`, `run_wholes` and the kernels' rules at
    the five classes' shapes (PR 66): the run is sized on the entries that
    grow, the table is its fixed entries and whole runs, and a table so
    made answers the same run."""
    assert [pa.run_pad(f, r) for f, r in ((0, 8), (1, 4), (34, 8), (33, 1),
                                          (8, 8))] == [0, 3, 6, 0, 0]
    assert pa.run_table_pages(1024, 0, 8) == 1024
    assert pa.run_table_pages(1024, 34, 1) == 1024
    # notes12k, docs16k, chat2k, turns4k, agent8k
    assert [pa.run_table_pages(*a) for a in (
        (1024, 34, 8), (1024, 1, 4), (160, 1, 4), (256, 1, 4),
        (512, 1, 8))] == [1026, 1025, 161, 257, 513]
    assert pa.run_table_pages(3, 34, 8) == 34       # under the fixed entries
    latent, k16, k8 = 16 * 640 * 2, 16 * 512 * 2, 16 * 256 * 2
    for table in (1024, 1025):
        assert pa.mla_walk_run_pages(latent, 16, table, 1) == 4
    for table, page, run in ((160, k16, 4), (161, k16, 4), (256, k16, 4),
                             (257, k16, 4), (512, k8, 8), (513, k8, 8)):
        assert pa.decode_walk_run_pages(page, 2 * page, 16, table, 1) == run
    # 32 KB a pool and more: a page a copy (Laguna's, Olmo's, InternLM2's)
    assert pa.decode_walk_run_pages(32 << 10, 64 << 10, 16, 512, 33) == 1
    assert pa.decode_walk_run_pages(16 * 30 * 128 * 2, 2 * 16 * 30 * 128 * 2,
                                    16, 192, 1) == 1
    # a run no longer than the least power of two that holds what grows,
    # and a divisor of the walk's block as long as can be
    assert pa.mla_walk_run_pages(latent, 16, 3, 1) == 2
    assert pa.run_table_pages(3, 1, 2) == 3
    assert pa.mla_walk_run_pages(latent, 16, 4, 1) == 4      # 3 -> 4
    assert pa.run_table_pages(4, 1, 4) == 5
    assert pa.mla_walk_run_pages(latent, 16, 5, 1) == 4
    assert pa.run_wholes(256, 0, lambda n: min(64, n)) == (256, 64)
    assert pa.run_wholes(160, 1, lambda n: min(64, n)) == (256, 64)
    # the walk's count with the first block's empty places
    assert pa.walk_counts(0, 64, 16, pad=3) == (0, 0)
    assert pa.walk_counts(61, 64, 16, pad=3) == pa.walk_counts(64, 64, 16)
    assert pa.walk_counts(62, 64, 16, pad=3) == pa.walk_counts(65, 64, 16)


# page bytes of a layer (all pools), table pages: the five configurations
CELLS = {
    "internlm2-1.8b": (2 * 16 * 8 * 128 * 2, 256),
    "laguna-xs.2 full": (2 * 16 * 8 * 128 * 2, 512),
    "laguna-xs.2 window": (2 * 16 * 8 * 128 * 2, 33),
    "olmo-hybrid-7b": (2 * 16 * 30 * 128 * 2, 192),
    "glm-4.7-flash": (16 * 640 * 2, 256),
    "mistral-7b-v0.1": (2 * 16 * 8 * 128 * 2, 2048),
}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_block_rule_at_the_configurations_shapes(name):
    """Whole pieces unless the table is the block, inside the budget of
    bytes and of positions, no longer than the table; the prefixes end at
    the block and never leave a lane more than twice what it holds."""
    page_bytes, table = CELLS[name]
    block = pa.walk_block_pages(page_bytes, 16, table)
    piece = pa.PIECE_POSITIONS // 16
    assert 1 <= block <= table
    assert block % piece == 0 or block == table
    assert (pa.BLOCK_SLOTS * block * page_bytes <= pa.WALK_BUFFER_BYTES
            or block == piece)
    assert block * 16 <= pa.BLOCK_POSITIONS
    # what the sweep chose (PERF.md section 6, PR 38)
    assert block == {"olmo-hybrid-7b": 32, "laguna-xs.2 window": 33}.get(
        name, 64)
    prefixes = pa.walk_prefixes(block, 16)
    assert prefixes[-1] == block and prefixes[0] == min(piece, block)
    assert list(prefixes) == sorted(set(prefixes))
    for pages in range(1, block + 1):
        blocks, attended = pa.walk_counts(pages, block, 16)
        assert blocks == 1
        assert pages * 16 <= attended < max(2 * pages, piece + 1) * 16
    # a full block is one update; what is behind it starts anew
    assert pa.walk_counts(3 * block, block, 16) == (3, 3 * block * 16)
    assert pa.walk_counts(3 * block + 1, block, 16) == (
        4, (3 * block + prefixes[0]) * 16)
    assert pa.walk_counts(0, block, 16) == (0, 0)


def test_a_wider_page_never_gets_more_pages():
    widths = [16 * 64 * n for n in (1, 5, 10, 32, 64, 120, 245, 1000)]
    blocks = [pa.walk_block_pages(w, 16, 4096) for w in widths]
    assert blocks == sorted(blocks, reverse=True)
    assert blocks[-1] == 8          # one piece at least, whatever it weighs
    assert blocks[0] == pa.BLOCK_POSITIONS // 16    # positions bound it
    assert pa.walk_block_pages(widths[0], 32, 4096) == blocks[0] // 2
    # and a table shorter than a piece is its own block
    assert pa.walk_block_pages(widths[3], 16, 5) == 5
    assert pa.walk_prefixes(5, 16) == (5,)


@pytest.mark.parametrize("name,pools", [
    ("Transformer", "k v"), ("MLAMoE", "kv"), ("GQAWindowMoE", "k v"),
    ("GQAWindowMoE", "wk wv"), ("HybridDelta", "k v")])
def test_a_model_asks_the_rule_what_its_kernel_asks(name, pools):
    """`model.walk_block_pages` (the engine's counts stand on it) is the
    rule at the bytes of a page of the pools the kernel is handed."""
    from ray_tpu.models import build_model
    from ray_tpu.models.gqa_window_moe import GQAWindowMoEConfig
    from ray_tpu.models.hybrid_delta import HybridDeltaConfig
    from ray_tpu.models.mla_moe import MLAMoEConfig
    model = build_model({"Transformer": tiny, "MLAMoE": MLAMoEConfig,
                         "GQAWindowMoE": GQAWindowMoEConfig,
                         "HybridDelta": HybridDeltaConfig}[name](), None)
    fixed = model.fixed_pages(16)
    cache = jax.eval_shape(lambda: model.init_cache(
        64, 16, **({"fixed_pages": 4 * fixed} if fixed else {})))
    page_bytes = sum(16 * cache[p].shape[3] * cache[p].dtype.itemsize
                     for p in pools.split())
    for table in (33, 256, 4096):
        asked = (model.walk_block_pages(16, table, fixed=True)
                 if pools == "wk wv" else model.walk_block_pages(16, table))
        assert asked == pa.walk_block_pages(page_bytes, 16, table)


@pytest.mark.parametrize("hd,page,dtype,tiles", [
    (128, 16, jnp.bfloat16, True), (128, 32, jnp.bfloat16, True),
    (256, 16, jnp.bfloat16, True), (128, 8, jnp.float32, True),
    (128, 8, jnp.bfloat16, False),      # half a bf16 tile a page
    (16, 16, jnp.bfloat16, False),      # the tiny model's heads
    (64, 16, jnp.bfloat16, False),      # half a lane, the row's width unsaid
])
def test_path_predicate(hd, page, dtype, tiles):
    assert pa.paged_decode_tiles(hd, page, dtype) is tiles
    # off a TPU the einsum runs whatever the shapes; for one, the shapes say
    with compute_platform("cpu"):
        assert not pa.uses_kernel(hd, page, dtype)
    with compute_platform("tpu"):
        assert pa.uses_kernel(hd, page, dtype) is tiles


@pytest.mark.parametrize("hd,kv_dim,tiles", [
    (64, 512, True), (64, 128, True), (64, 192, False), (64, 64, False),
    (32, 128, True), (16, 128, True), (48, 384, False), (128, 512, True),
    (192, 384, False), (256, 512, True),
])
def test_path_predicate_of_a_head_that_shares_a_lane(hd, kv_dim, tiles):
    assert pa.paged_decode_tiles(hd, 16, jnp.bfloat16, kv_dim) is tiles
    assert not pa.paged_decode_tiles(hd, 8, jnp.bfloat16, kv_dim)


def test_kernel_refuses_shapes_it_does_not_tile():
    q = jnp.zeros((1, 2, 16), jnp.bfloat16)
    pool = jnp.zeros((1, 4, 8, 32), jnp.bfloat16)
    with pytest.raises(ValueError, match="does not tile"):
        pa.paged_decode_attention_kernel(
            q, pool, pool, 0, jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1,), jnp.int32))


# ------------------------------------------------ the pool, in place
@pytest.fixture(scope="module")
def core():
    cfg = tiny()
    params = Transformer(cfg).init(jax.random.PRNGKey(0))
    return EngineCore(cfg, params, num_pages=32, page_size=8, max_batch=2)


def _compiled(core, which):
    B, P = core.max_batch, core.max_pages_per_seq
    if which == "step":
        lowered = core._decode_fn.lower(
            core.params, core._cache, jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.zeros((B, P), jnp.int32),
            jnp.zeros((B,), bool))
    else:
        lowered = core._prefill_fn(16).lower(
            core.params, jnp.zeros((16,), jnp.int32), jnp.int32(3),
            jnp.zeros((P,), jnp.int32), core._cache)
    return lowered.compile()


@pytest.mark.parametrize("which", ["step", "pre"])
def test_compiled_program_updates_the_pool_in_place(core, which):
    compiled = _compiled(core, which)
    pool = core._cache["k"]
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= 2 * pool.nbytes)
    shape = ",".join(map(str, pool.shape))
    made = re.findall(
        rf"= \w+\[{shape}\]\S* (\w[\w\-]*)\(", compiled.as_text())
    assert made and "copy" not in made
    # nothing of the pool's shape but the scatters into it (a fusion
    # that holds one, on this backend)
    assert set(made) <= {"scatter", "fusion", "parameter",
                         "get-tuple-element", "bitcast"}, made


def test_a_caller_that_keeps_the_old_cache_holds_nothing(core):
    old = core._cache
    core.submit([1, 2, 3], max_tokens=2, rid="x")
    while core.has_work:
        core.step()
    assert old["k"].is_deleted() and old["v"].is_deleted()
    assert not core._cache["k"].is_deleted()


def test_kernel_on_a_mesh_splits_kv_heads_over_tp():
    """On more than one device the call is shard-mapped, kv heads over
    `tp` as `models.decode.cache_sharding` lays the pool."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.parallel.mesh import MeshSpec
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    mesh = MeshSpec(dp=1, tp=2).build(jax.devices()[:2])
    q, k, v, pt, ln = _case([70, 0, 200], kvh=4, group=2, seed=5)
    want = pa.paged_attention_reference(q, k, v, 1, pt, ln)
    on_mesh = NamedSharding(mesh, P(None, None, None, "tp"))
    k, v = jax.device_put(k, on_mesh), jax.device_put(v, on_mesh)
    got = jax.jit(lambda *a: pa.paged_decode_attention_kernel(
        *a, mesh=mesh))(q, k, v, jnp.int32(1), pt, ln)
    _close(got, want)
