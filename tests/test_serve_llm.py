"""ray_tpu.serve.llm: continuous batching, paged KV cache, streaming.

Tier-1 exercises the engine in-process on the CPU backend (no cluster):
per-iteration admission ordering, page alloc/free across prefill/
decode/eviction, stop/max-token termination, the push token stream
with incarnation fencing. The slow e2e deploys two replica
groups through serve and streams two concurrent generations of
different lengths end to end.
"""
import queue
import threading
import time

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from llm_streams import read_stream
from ray_tpu.models.config import tiny
from ray_tpu.models.transformer import Transformer
from ray_tpu.serve.llm.engine import (FINISH_LENGTH, FINISH_STOP,
                                      EngineCore, LLMEngine)
from ray_tpu.serve.llm.kv_cache import (PageAllocator,
                                        pages_from_budget, pages_needed)


# ------------------------------------------------------------- kv cache
def test_page_allocator_alloc_free():
    a = PageAllocator(4)
    assert a.free_pages == 4
    got = a.alloc(3)
    assert got is not None and len(got) == 3
    assert a.free_pages == 1 and a.used_pages == 3
    # all-or-nothing: 2 > 1 free -> None, nothing consumed
    assert a.alloc(2) is None
    assert a.free_pages == 1
    a.free(got[:2])
    assert a.free_pages == 3
    with pytest.raises(ValueError):
        a.free(got[:1] + got[:1])       # double free in one call
    a2 = PageAllocator(2)
    p = a2.alloc(1)
    a2.free(p)
    with pytest.raises(ValueError):
        a2.free(p)                      # double free across calls


# a script of calls and what the allocator without runs answered to it
# (ids in order, None where short; after a free, pages free and used), at
# 19 pages with `fixed=2, sequences=3` and with one class: recorded from
# the tree before PR 62, which `run=1` must answer alike
_SCRIPT = (
    [["free", "b", 0], ["free", "d", 1], ["alloc", "b", 1], ["alloc", "a", 1],
    ["free", "a", 2], ["alloc", "a", 2], ["alloc", "b", 1], ["alloc", "c", 5],
    ["alloc", "a", 2], ["alloc", "c", 1], ["alloc", "c", 5], ["free", "d", 1],
    ["free", "b", 0], ["free", "b", 0], ["free", "b", 1], ["alloc", "d", 2],
    ["alloc", "b", 5], ["free", "b", 2], ["free", "d", 2], ["alloc", "a", 5],
    ["free", "a", 0], ["free", "d", 0], ["free", "c", 0], ["free", "d", 1],
    ["free", "c", 2], ["alloc", "c", 3], ["alloc", "c", 3], ["alloc", "a", 2],
    ["alloc", "d", 1], ["free", "b", 0]])
_RECORDED = {
    (("fixed", 2), ("sequences", 3)):
        [[19, 0], [19, 0], [0], [1], [18, 1], [1, 2], [3], [4, 5, 6, 7, 8],
        [9, 10], [11], [12, 13, 14, 15, 16], [2, 17], [4, 15], [4, 15], [4,
        15], [3, 0], None, [2, 17], [4, 15], None, [8, 11], [8, 11], [19,
        0], [19, 0], [19, 0], [5, 4, 16], [15, 14, 13], [2, 1], [0], [10,
        9]],
    ():
        [[19, 0], [19, 0], [0], [1], [18, 1], [1, 2], [3], [4, 5, 6, 7, 8],
        [9, 10], [11], [12, 13, 14, 15, 16], [2, 17], [4, 15], [4, 15], [4,
        15], [3, 0], None, [2, 17], [4, 15], None, [8, 11], [8, 11], [19,
        0], [19, 0], [19, 0], [16, 15, 14], [13, 12, 11], [8, 7], [6], [10,
        9]]}


def _play(script, **kw):
    """The script's calls on a new allocator, every sequence telling what
    it holds; a free of 0 gives back all of it, of n its last n."""
    a, held, out = PageAllocator(**kw), {}, []
    for op, who, n in script:
        mine = held.setdefault(who, [])
        if op == "alloc":
            got = a.alloc(n, held=len(mine))
            mine.extend(got or [])
            out.append(got)
        else:
            back = mine[len(mine) - n:] if n else list(mine)
            del mine[len(mine) - len(back):]
            a.free(back)
            out.append([a.free_pages, a.used_pages])
    return out


@pytest.mark.parametrize("kw", list(_RECORDED), ids=["two-classes", "one"])
def test_page_allocator_without_runs_answers_as_it_did(kw):
    assert _play(_SCRIPT, num_pages=19, run=1, **dict(kw)) == _RECORDED[kw]
    assert _play(_SCRIPT, num_pages=19, **dict(kw)) == _RECORDED[kw]


@pytest.mark.parametrize("run", [1, 4, 8])
def test_page_allocator_hands_out_whole_aligned_runs(run):
    """Pages of the class that grows come and go in runs `[g x run, g x
    run + run)`: what a sequence holds is rounded up to whole runs."""
    def runs_of(pages):
        """The first page of each run `pages` is made of, checked."""
        starts = pages[::run]
        assert all(start % run == 0 for start in starts)
        assert pages == [start + i for start in starts for i in range(run)]
        return starts

    # a pool that is not whole runs: the tail is nobody's
    a = PageAllocator(5 * run + run // 2, run=run)
    assert a.unused_pages == run // 2 and a.free_pages == 5 * run
    assert a.fits(5 * run) and a.fits(4 * run + 1)
    assert not a.fits(5 * run + 1)
    # one page asked: a whole run got
    x = a.alloc(1)
    assert len(runs_of(x)) == 1 and a.used_pages == run
    # inside a run the sequence holds what it needs; at its end a run more
    if run > 1:
        assert a.alloc(1, held=1) == [] == a.alloc(run - 1, held=1)
        assert a.used_pages == run
    more = a.alloc(1, held=len(x))
    assert len(runs_of(x + more)) == 2
    y = a.alloc(2 * run + 1)
    assert len(runs_of(y)) == 3 and not set(y) & set(x + more)
    assert a.free_pages == 0 and a.used_pages == 5 * run
    # all-or-nothing: none left, nothing claimed
    assert a.alloc(1) is None and a.free_pages == 0
    # free and reuse: a run comes back with the last of its pages
    a.free(more[1:])
    assert a.free_pages == 0
    a.free(more[:1])
    assert a.free_pages == run and a.alloc(run + 1) is None
    assert sorted(a.alloc(run)) == sorted(more)
    # double free, in one call and across calls
    a.free(more)
    with pytest.raises(ValueError):
        a.free(more[:1])
    with pytest.raises(ValueError):
        a.free(x[:1] + x[:1])
    # every id handed out over a shuffled life lies in a whole aligned run
    b = PageAllocator(16 * run, run=run)
    rng, held = np.random.default_rng(run), {}
    for _ in range(200):
        mine = held.setdefault(int(rng.integers(5)), [])
        if mine and rng.random() < 0.2:
            b.free(mine)
            mine.clear()
        else:
            mine.extend(b.alloc(int(rng.integers(1, 4)), held=len(mine))
                        or [])
        runs_of(mine)
        every = [p for pages in held.values() for p in pages]
        assert len(every) == len(set(every)) == b.used_pages
    # a fixed class is what it was: ids 0 .., one at a time; the runs
    # start at the first multiple of `run` behind it
    c = PageAllocator(3 + 4 * run + 1, fixed=1, sequences=3, run=run)
    plain = PageAllocator(3 + 4 * run + 1, fixed=1, sequences=3)
    assert c.fixed_pages == plain.fixed_pages == 3
    # (of 20 pages 4 .. 19 are runs of 4, of 36 pages 8 .. 31 runs of 8)
    assert c.unused_pages == {1: 0, 4: 1, 8: 9}[run]
    got = c.alloc(2)
    assert got[0] == plain.alloc(2)[0] == 0
    assert got[1] >= 3 and len(runs_of(got[1:])) == 1
    assert len(runs_of(c.alloc(1, held=len(got)))) == 1
    assert c.alloc(1)[0] == plain.alloc(1)[0] == 1
    assert c.fixed_used == 2
    c.free(got)
    assert c.fixed_used == 1 and c.alloc(1)[0] == 0


def test_an_engine_over_a_latent_class_lays_and_walks_runs(monkeypatch):
    """What PR 62's engine did for `SparseMLAMoE`'s walks it does for the
    latent kernel's (interpreted here, the mixer made to answer 4): the
    run the class answers is the allocator's, the kernel's (through the
    step's `Walk`) and the counters', across admission, growth, an eviction
    and a cancel, and the tokens are those of a page at a time."""
    import dataclasses
    from ray_tpu.models.latent import LatentAttention
    from ray_tpu.models.mla_moe import MLAMoE, tiny_mla_moe
    from ray_tpu.ops import paged_attention as pa
    from test_sparse_mla_moe import _serve_in_runs
    handed = []
    call = pa._mla_paged_decode_call

    def interpreted(*a, run=1, fixed=0):
        handed.append(run)
        return call(*a[:-1], True, run=run, fixed=fixed)
    monkeypatch.setattr(pa, "mla_uses_kernel", lambda *a: True)
    monkeypatch.setattr(pa, "_mla_paged_decode_call", interpreted)
    # rows of 256 that hold a latent of 128: shapes the kernel tiles
    cfg = dataclasses.replace(tiny_mla_moe(), kv_lora_rank=128)
    params = MLAMoE(cfg).init(jax.random.PRNGKey(0))
    got, core = _serve_in_runs(monkeypatch, cfg, params, 4, LatentAttention)
    assert core.alloc.run == 4 and core.alloc.unused_pages == 1
    assert core.cache_stats()["page_run"] == 4
    assert core.device_stats()["decode_attention"] == "mla_paged_decode_attn"
    assert set(handed) == {4}               # one trace, every layer's call
    c = core.counters
    assert c["evictions"] > 0
    assert len(got["a"]) == 40 and len(got["b"]) == len(got["d"]) == 24
    assert len(got["c"]) == 5
    # a copy a run: whole runs read, up to 3 pages a lane beyond the live
    assert c["kv_positions_read"] == c["kv_walk_copies"] * 4 * 8
    assert c["kv_positions_live"] <= c["kv_positions_read"] \
        < c["kv_positions_live"] + c["decode_lane_steps"] * 4 * 8
    del handed[:]
    want, plain = _serve_in_runs(monkeypatch, cfg, params, 1,
                                 LatentAttention)
    assert plain.alloc.run == 1 and "page_run" not in plain.cache_stats()
    assert set(handed) == {1}
    p = plain.counters
    assert p["kv_positions_read"] == p["kv_walk_copies"] * 8
    assert got == want


# ------------------- runs behind a sequence's fixed entries (PR 66)
def _laid_behind_fixed(pages, fixed, run, fixed_pages):
    """A sequence's entries in table order: the first `fixed` of the fixed
    class, then whole runs, each `run` ids behind one another from a
    multiple of `run` on."""
    head, grown = pages[:fixed], pages[fixed:]
    assert all(0 <= p < fixed_pages for p in head)
    starts = grown[::run]
    assert all(p % run == 0 and p >= fixed_pages for p in starts)
    assert grown == [p + i for p in starts for i in range(run)]


@pytest.mark.parametrize("fixed,run", [(1, 4), (1, 8), (34, 4), (34, 8)])
def test_page_allocator_lays_whole_runs_behind_the_fixed_entries(fixed, run):
    """Over a shuffled life of five sequences every held entry `fixed + k x
    run` is a multiple of `run` with its run behind it, the fixed entries
    come first and one by one, and a sequence at full length fits the
    table `run_table_pages` makes (its last run whole)."""
    from ray_tpu.ops.paged_attention import run_table_pages
    lanes, full = 5, 50                 # pages of a full-length sequence
    table = run_table_pages(full, fixed, run)
    assert (table - fixed) % run == 0 and full <= table < full + run
    first = -(-lanes * fixed // run) * run
    alloc = PageAllocator(first + lanes * (table - fixed), fixed=fixed,
                          sequences=lanes, run=run)
    # (the pages between the fixed class and the first run are nobody's)
    assert alloc.fixed_pages == lanes * fixed
    assert alloc.unused_pages == first - lanes * fixed < run
    rng, held = np.random.default_rng(fixed * run), {}
    for _ in range(300):
        mine = held.setdefault(int(rng.integers(lanes)), [])
        if mine and rng.random() < 0.15:
            alloc.free(mine)
            mine.clear()
        elif len(mine) < full:
            want = min(int(rng.integers(1, 9)), full - len(mine))
            mine.extend(alloc.alloc(want, held=len(mine)))
        _laid_behind_fixed(mine, fixed, run, alloc.fixed_pages)
        assert len(mine) <= table
        every = [p for pages in held.values() for p in pages]
        assert len(every) == len(set(every)) == alloc.used_pages
    # every lane at full length at once: each fits its table
    for mine in held.values():
        mine.extend(alloc.alloc(full - len(mine), held=len(mine))
                    if len(mine) < full else [])
        assert full <= len(mine) == table
        _laid_behind_fixed(mine, fixed, run, alloc.fixed_pages)
    assert alloc.fits(full) and alloc.free_pages == 0


def _count_dispatches(core):
    """Wraps `core`'s decode program: `count()` gives (copies, positions
    read) as a count over the tables it was handed says: a lane `n` pages
    long copies its first `fixed` entries a page each and a run each of
    those behind them, all held and laid as the allocator lays them."""
    real, seen = core._decode_fn, []

    def spied(params, cache, tokens, positions, pts, active):
        seen.append(tuple(np.asarray(a) for a in (positions, pts, active)))
        return real(params, cache, tokens, positions, pts, active)
    core._decode_fn = spied

    def count():
        fixed, run, size = core._fixed, core.alloc.run, core.page_size
        fixed = fixed if run > 1 else 0
        copies = read = 0
        for positions, pts, active in seen:
            assert pts.shape == (core.max_batch, core.max_pages_per_seq)
            for lane in np.flatnonzero(active):
                n = positions[lane] // size + 1
                entries = list(range(min(n, fixed))) + list(
                    range(fixed, n, run))
                assert (pts[lane, entries] >= 0).all()
                assert not (pts[lane, [e for e in entries if e >= fixed]]
                            % run).any()
                copies += len(entries)
                read += (min(n, fixed)
                         + run * sum(e >= fixed for e in entries)) * size
        return copies, read
    return count


def _serve_counted(cfg, params, prompts, max_tokens, **engine):
    """An engine's tokens by request, every sequence's entries checked
    after every step and its counters against `_count_dispatches`."""
    core = EngineCore(cfg, params, **engine)
    count = _count_dispatches(core)
    for rid, prompt in prompts.items():
        core.submit(prompt, max_tokens=max_tokens, rid=rid)
    got = {rid: [] for rid in prompts}
    for _ in range(200):
        if not core.has_work:
            break
        for ev in core.step():
            if ev["token"] is not None:
                got[ev["rid"]].append(ev["token"])
        for seq in core._running:
            _laid_behind_fixed(seq.pages, core._fixed, core.alloc.run,
                               core.alloc.fixed_pages)
            assert len(seq.pages) <= core.max_pages_per_seq
    assert not core.has_work and core.alloc.used_pages == 0
    c = core.counters
    assert (c["kv_walk_copies"], c["kv_positions_read"]) == count()
    assert c["evictions"] == 0
    return got, core


def test_an_engine_lays_and_walks_runs_behind_a_state_slot(monkeypatch):
    """A per-head class that keeps a slot (`fixed` 1: `ParallelHybrid`, its
    mixer made to answer 4, its kernel interpreted): the table is the slot's
    entry and whole runs, the kernel is handed the run and `fixed`, the
    counters are a count over the tables, and the tokens are those of a
    page at a time."""
    import dataclasses
    from ray_tpu.models.gqa import Attention
    from ray_tpu.models.parallel_hybrid import (ParallelHybrid,
                                                tiny_parallel_hybrid)
    from ray_tpu.ops import paged_attention as pa
    handed = []
    call = pa._paged_decode_call

    def interpreted(*a, interpret, run=1, fixed=0, **kw):
        handed.append((run, fixed))
        return call(*a, interpret=True, run=run, fixed=fixed, **kw)
    monkeypatch.setattr(pa, "uses_kernel", lambda *a: True)
    monkeypatch.setattr(pa, "_paged_decode_call", interpreted)
    cfg = dataclasses.replace(tiny_parallel_hybrid(), n_heads=2,
                              n_kv_heads=1, head_dim=128, max_seq_len=128)
    params = ParallelHybrid(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = {"a": rng.integers(0, 256, 21).tolist(), "b": [5, 6, 7],
               "c": rng.integers(0, 256, 40).tolist()}
    served = {}
    for run in (4, 1):
        monkeypatch.setattr(Attention, "page_run", lambda *a, run=run: run)
        del handed[:]
        served[run], core = _serve_counted(cfg, params, prompts, 12,
                                           page_size=8, max_batch=3)
        assert core.alloc.run == run and core._fixed == 1
        assert core.max_pages_per_seq == {4: 1 + 16, 1: 16}[run]
        assert set(handed) == {(run, 1)}
        assert core.device_stats()["decode_attention"].startswith(
            "paged_decode_attn")
        c = core.counters
        assert c["kv_positions_live"] <= c["kv_positions_read"] \
            < c["kv_positions_live"] + c["decode_lane_steps"] * run * 8
        pages = c["kv_positions_read"] / 8 / c["kv_walk_copies"]
        assert (1 < pages < 4) if run == 4 else pages == 1
    assert served[4] == served[1]
    assert all(len(tokens) == 12 for tokens in served[4].values())


def test_an_engine_lays_and_walks_runs_behind_a_ring(monkeypatch):
    """The class of a ring and two sparse walks (`fixed` 34 at a window of
    260 in pages of 8), its walks interpreted: the class answers a run from
    its index keys' page, the table is the ring's 34 entries and whole
    runs, sequences past the ring hold runs that both walks copy whole, the
    ring's own walk still reads its first 34 entries, and the tokens are
    those of a page at a time."""
    from ray_tpu.models.sparse_mla_moe import SparseLatentAttention
    from ray_tpu.models.sparse_window_mla_moe import (
        SparseWindowMLAMoE, tiny_sparse_window_mla_moe)
    from test_sparse_mla_moe import _interpreted
    import dataclasses
    from ray_tpu.ops import paged_attention as pa
    _interpreted(monkeypatch, index_pages=16, attend_pages=8)
    ring_call = pa._mla_paged_window_decode_call
    monkeypatch.setattr(pa, "mla_uses_kernel", lambda *a: True)
    monkeypatch.setattr(pa, "_mla_paged_window_decode_call",
                        lambda *a: ring_call(*a[:-1], True))
    cfg = dataclasses.replace(
        tiny_sparse_window_mla_moe(index_topk=16, sliding_window=260),
        max_seq_len=512)
    params = SparseWindowMLAMoE(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = {"a": rng.integers(0, 256, 300).tolist(),     # past the ring
               "b": rng.integers(0, 256, 270).tolist(),     # grows past it
               "c": [5, 6, 7]}                              # inside it
    got, core = _serve_counted(cfg, params, prompts, 10, page_size=8,
                               max_batch=3, num_pages=3 * 34 + 2 + 3 * 32)
    # 256 B a page of index keys: 64 wanted, cut to the 32 that hold the 30
    # entries that grow and to the walks' blocks of 16 and 8
    assert (core._fixed, core.alloc.run) == (34, 8)
    assert core.max_pages_per_seq == 34 + 32
    assert core.device_stats()["decode_attention"].startswith(
        "dsa_paged_attend")
    c = core.counters
    assert c["dsa_lanes_past_topk"] > 0 and c["ring_positions_read"] > 0
    pages = c["kv_positions_read"] / 8 / c["kv_walk_copies"]
    assert 1 < pages < 8
    monkeypatch.setattr(SparseLatentAttention, "page_run", lambda *a: 1)
    want, plain = _serve_counted(cfg, params, prompts, 10, page_size=8,
                                 max_batch=3, num_pages=3 * 34 + 2 + 3 * 32)
    assert (plain.alloc.run, plain.max_pages_per_seq) == (1, 64)
    p = plain.counters
    assert p["kv_positions_read"] == p["kv_walk_copies"] * 8
    assert got == want and all(len(t) == 10 for t in got.values())


def test_pages_needed_and_budget():
    assert pages_needed(1, 16) == 1
    assert pages_needed(16, 16) == 1
    assert pages_needed(17, 16) == 2
    cfg = tiny()
    n1 = pages_from_budget(cfg, 16, 1 << 20)
    assert n1 >= 1
    # sharding the kv heads across tp shrinks the per-shard page, so
    # the same budget holds more pages
    n2 = pages_from_budget(cfg, 16, 1 << 20, tp_shards=2)
    assert n2 >= n1


# --------------------------------------------------------- core fixture
@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _drain(core, max_steps=200):
    """Step until idle; returns finish order [(rid, reason)] and all
    events."""
    order, events = [], []
    for _ in range(max_steps):
        evs = core.step()
        events.extend(evs)
        for e in evs:
            if e["done"]:
                order.append((e["rid"], e["reason"]))
        if not core.has_work:      # the last token has been read
            break
    return order, events


def test_decode_matches_full_forward(tiny_model):
    """Greedy prefill+paged-decode must be bit-identical to running the
    whole transformer over the growing sequence."""
    cfg, model, params = tiny_model
    core = EngineCore(cfg, params, num_pages=32, page_size=8,
                      max_batch=2)
    prompt = [3, 17, 91, 254, 8, 44]
    core.submit(prompt, max_tokens=5, rid="g")
    order, events = _drain(core)
    got = [e["token"] for e in events if e["rid"] == "g"
           and e["token"] is not None]
    # reference: greedy full-forward, one token at a time
    toks = list(prompt)
    ref = []
    for _ in range(5):
        logits = model.apply(params, jnp.array([toks]))
        nxt = int(jnp.argmax(logits[0, len(toks) - 1]))
        ref.append(nxt)
        toks.append(nxt)
    assert got == ref


def test_admission_interleaves_prefill_and_decode(tiny_model):
    """A new request prefills in the same iteration an in-flight one
    decodes (the call returns the new one's first token beside the
    token of the decode step the call before dispatched) — and a short
    generation submitted after a long one still finishes first
    (continuous batching, not run-to-completion)."""
    cfg, model, params = tiny_model
    core = EngineCore(cfg, params, num_pages=64, page_size=8,
                      max_batch=4)
    core.submit(list(range(1, 9)), max_tokens=24, rid="long")
    first = core.step()
    assert [e["rid"] for e in first if e["first"]] == ["long"]
    core.submit(list(range(20, 24)), max_tokens=3, rid="short")
    assert core.counters["decode_steps"] == 1     # long's, not read yet
    mixed = core.step()
    kinds = {(e["rid"], e["first"], e["seq"]) for e in mixed}
    # the same step admits (prefills) short AND decodes long: it reads
    # long's first decode step, one step behind the two it dispatched
    assert kinds == {("short", True, 0), ("long", False, 1)}
    assert core.counters["decode_lane_steps"] == 1 + 2
    order, _ = _drain(core)
    assert order[0] == ("short", FINISH_LENGTH)
    assert order[-1][0] == "long"
    assert core.stats()["free_pages"] == 64      # everything released


def test_stop_and_max_token_termination(tiny_model):
    cfg, model, params = tiny_model
    core = EngineCore(cfg, params, num_pages=32, page_size=8,
                      max_batch=2)
    # discover the first greedy token, then use it as the stop token
    core.submit([5, 6, 7], max_tokens=8, rid="probe")
    order, events = _drain(core)
    assert order == [("probe", FINISH_LENGTH)]
    toks = [e["token"] for e in events if e["token"] is not None]
    assert len(toks) == 8
    core.submit([5, 6, 7], max_tokens=8, rid="stopped",
                stop=(toks[0],))
    order, events = _drain(core)
    assert order == [("stopped", FINISH_STOP)]
    # the stop token is emitted, then the sequence retires
    got = [e["token"] for e in events if e["token"] is not None]
    assert got == [toks[0]]
    assert core.stats()["free_pages"] == 32


def test_submit_validation(tiny_model):
    cfg, model, params = tiny_model
    core = EngineCore(cfg, params, num_pages=4, page_size=8,
                      max_batch=2)
    with pytest.raises(ValueError):
        core.submit([], max_tokens=4)
    with pytest.raises(ValueError):
        core.submit([1], max_tokens=0)
    with pytest.raises(ValueError):
        # 4 pages * 8 slots = 32 positions max per seq here
        core.submit([1] * 30, max_tokens=10)


def test_eviction_requeues_with_emitted_preserved(tiny_model):
    """Page exhaustion mid-decode evicts the youngest sequence back to
    the waiting queue; because re-prefill covers prompt+emitted, the
    evicted request's final tokens match an uninterrupted run."""
    cfg, model, params = tiny_model
    # reference: roomy pool, no eviction possible
    ref_core = EngineCore(cfg, params, num_pages=32, page_size=4,
                          max_batch=2)
    ref_core.submit([9, 8, 7, 6], max_tokens=10, rid="b")
    _, ref_events = _drain(ref_core)
    ref_toks = [e["token"] for e in ref_events if e["token"] is not None]
    assert len(ref_toks) == 10

    # tight pool: two seqs can't both grow; someone gets evicted
    core = EngineCore(cfg, params, num_pages=4, page_size=4,
                      max_batch=2)
    core.submit([1, 2, 3, 4], max_tokens=10, rid="a")
    core.submit([9, 8, 7, 6], max_tokens=10, rid="b")
    order, events = _drain(core, max_steps=400)
    assert core.stats()["evictions"] >= 1
    assert sorted(r for r, _ in order) == ["a", "b"]
    got_b = [e["token"] for e in events if e["rid"] == "b"
             and e["token"] is not None]
    # duplicates are possible across an eviction (tokens re-derived are
    # NOT re-emitted; emitted is preserved) — the stream stays exact
    assert got_b == ref_toks
    assert core.stats()["free_pages"] == 4


# ------------------------------------------- the pipeline, both models
@pytest.fixture(scope="module", params=["dense", "mla_moe"])
def served(request):
    """(config, params) of each tiny model the engine serves."""
    if request.param == "dense":
        cfg = tiny()
        return cfg, Transformer(cfg).init(jax.random.PRNGKey(0))
    from ray_tpu.models.mla_moe import MLAMoE, tiny_mla_moe
    cfg = tiny_mla_moe()
    return cfg, MLAMoE(cfg).init(jax.random.PRNGKey(0))


def _alone(served, prompt, max_tokens, stop=()):
    """The reference: the request by itself in a roomy engine (one a
    model, kept: its programs compile once)."""
    cfg, params = served
    core = _ALONE.get(id(params))
    if core is None:
        core = _ALONE[id(params)] = EngineCore(
            cfg, params, num_pages=32, page_size=4, max_batch=1)
    core.submit(prompt, max_tokens=max_tokens, stop=stop, rid="r")
    _, events = _drain(core)
    return [e["token"] for e in events]


_ALONE = {}


def test_every_request_gets_the_tokens_it_gets_alone(served):
    """A mixed schedule through three lanes and a pool that runs dry:
    admission mid-flight, a stop token, `max_tokens` 1, a cancel with a
    step in flight, eviction. Every token is the one the request gets
    when it is served alone."""
    cfg, params = served
    prompts = {"long": [3, 17, 91, 254, 8, 1], "one": [5, 6, 7],
               "stop": [9, 8, 7, 6], "mid": [200, 100, 50, 25, 12],
               "gone": [4, 4, 4], "late": [2, 3]}
    budget = {"long": 14, "one": 1, "stop": 12, "mid": 9, "gone": 12,
              "late": 6}
    free = {rid: _alone(served, prompts[rid], n)
            for rid, n in budget.items()}
    # stop at the third token's first occurrence
    stop = free["stop"][2]
    want = dict(free, stop=free["stop"][:free["stop"].index(stop) + 1])
    assert want["stop"] == _alone(served, prompts["stop"], 12, (stop,))

    core = EngineCore(cfg, params, num_pages=7, page_size=4, max_batch=3)
    for rid in ("long", "one", "stop", "gone"):
        core.submit(prompts[rid], max_tokens=budget[rid], rid=rid,
                    stop=(stop,) if rid == "stop" else ())
    got = {rid: [] for rid in prompts}
    reasons = {}
    for i in range(200):
        if i == 3:
            core.submit(prompts["mid"], max_tokens=9, rid="mid")
        if i == 4:
            assert core._flight is not None       # a step in flight
            assert any(s.rid == "gone" for _, s in core._flight.lanes)
            n_gone = len(got["gone"])
            assert core.cancel("gone")
            core.submit(prompts["late"], max_tokens=6, rid="late")
        if not core.has_work:
            break
        for ev in core.step():
            got[ev["rid"]].append(ev["token"])
            assert ev["seq"] == len(got[ev["rid"]]) - 1
            if ev["done"]:
                reasons[ev["rid"]] = ev["reason"]
    assert not core.has_work and core._flight is None
    assert core.counters["evictions"] >= 1
    assert core.counters["pipeline_flushes"] >= 1
    gone = got.pop("gone")
    assert len(gone) == n_gone and gone == free["gone"][:n_gone]
    assert got == {rid: want[rid] for rid in got}
    assert reasons == {"long": FINISH_LENGTH, "one": FINISH_LENGTH,
                       "stop": FINISH_STOP, "mid": FINISH_LENGTH,
                       "late": FINISH_LENGTH}
    assert core.stats()["free_pages"] == 7
    assert core._lanes == [None] * 3 and not core._running


def test_drain_reads_the_step_in_flight_and_a_survivor_continues(served):
    cfg, params = served
    prompt = [3, 17, 91, 254, 8]
    want = _alone(served, prompt, 9)
    core = EngineCore(cfg, params, num_pages=32, page_size=4, max_batch=2)
    core.submit(prompt, max_tokens=9, rid="d")
    core.submit([1, 2], max_tokens=3, rid="e")
    seen = [e["token"] for _ in range(2) for e in core.step()
            if e["rid"] == "d"]
    assert core._flight is not None
    events, descs = core.drain()
    # e's last step was in flight: it ended with the drain's events and
    # is no one's to resume
    assert [(e["rid"], e["done"]) for e in events] == [
        ("d", False), ("e", True)]
    assert [d["rid"] for d in descs] == ["d"]
    emitted = descs[0]["emitted"]
    assert emitted == seen + [events[0]["token"]] == want[:len(emitted)]
    assert not core.has_work and core.stats()["free_pages"] == 32
    assert core.counters["pipeline_flushes"] == 1
    # the survivor re-prefills prompt + emitted and goes on from there
    rest = _alone(served, descs[0]["prompt"] + emitted,
                  descs[0]["max_tokens"] - len(emitted))
    assert emitted + rest == want


def test_a_lane_freed_by_max_tokens_is_taken_in_the_next_dispatch(served):
    """Four requests of two decode steps each through two lanes: a lane
    comes free when its sequence's last step is dispatched, a step before
    that step's token is read, and the request that waits takes it in the
    next call — four full decode steps, no lane-step empty."""
    cfg, params = served
    core = EngineCore(cfg, params, num_pages=32, page_size=4, max_batch=2)
    for i in range(4):
        core.submit([10 + i, 20 + i, 30 + i], max_tokens=3, rid=f"r{i}")
    lanes = []
    while core.has_work:
        before = core.counters["decode_lane_steps"]
        waiting = len(core._waiting)
        core.step()
        stepped = core.counters["decode_lane_steps"] - before
        if waiting:
            assert stepped == 2     # nobody waits beside an empty lane
        lanes.append(stepped)
    assert lanes == [2, 2, 2, 2, 0]
    assert core.counters["decode_steps"] == 4
    assert core.counters["decode_steps_ahead"] == 3
    assert core.counters["finished"] == 4


@pytest.mark.parametrize("at", [0, 1, 3])
def test_a_stop_token_costs_one_discarded_lane_step(served, at):
    """The stop token is known a step late: the lane runs one step more,
    whose token nobody gets, and then every page is free. Token 0 is the
    prefill's."""
    cfg, params = served
    prompt = [5, 6, 7]
    free = _alone(served, prompt, 8)
    stop = free[at]
    want = free[:free.index(stop) + 1]
    core = EngineCore(cfg, params, num_pages=16, page_size=4, max_batch=2)
    core.submit(prompt, max_tokens=8, stop=(stop,), rid="s")
    order, events = _drain(core)
    assert order == [("s", FINISH_STOP)]
    assert [e["token"] for e in events] == want    # the stop token last
    c = core.counters
    assert c["discarded_lane_steps"] == 1
    assert c["decode_lane_steps"] == len(want)     # one past the last
    assert core.stats()["free_pages"] == 16 and core._flight is None


def test_cancel_with_a_step_in_flight_leaves_an_idle_core(served):
    """What the benchmark's harness does after a window: cancel whatever
    is open, require `has_work` false, then call the compiled programs
    directly on `core._cache`. The dispatch nobody owns is abandoned."""
    cfg, params = served
    core = EngineCore(cfg, params, num_pages=16, page_size=4, max_batch=2)
    core.submit([1, 2, 3], max_tokens=20, rid="a")
    core.submit([4, 5, 6, 7], max_tokens=20, rid="b")
    core.step()
    core.step()
    assert core._flight is not None and core.has_work
    assert core.cancel("a") and core.has_work
    assert core.cancel("b")
    assert not core.has_work and core._flight is None
    assert core.counters["discarded_lane_steps"] == 2
    assert core.stats()["free_pages"] == 16
    assert core.step() == []            # nothing is waited for
    # the harness's check: a prefill and a decode step of its own
    pages = core.alloc.alloc(2)
    pt = np.full((core.max_pages_per_seq,), -1, np.int32)
    pt[:2] = pages
    logits, core._cache = core._prefill_fn(16)(
        core.params, jnp.zeros((16,), jnp.int32), jnp.int32(3),
        jnp.asarray(pt), core._cache)
    assert logits.shape == (cfg.vocab_size,)
    B = core.max_batch
    pts = np.full((B, core.max_pages_per_seq), -1, np.int32)
    pts[1] = pt
    logits, core._cache = core._decode_fn(
        core.params, core._cache, jnp.asarray([0, 9], jnp.int32),
        jnp.asarray([0, 3], jnp.int32), jnp.asarray(pts),
        jnp.asarray([False, True]))
    assert logits.shape == (B, cfg.vocab_size)
    # tokens made on the host and tokens left on the device are one
    # program's argument: nothing compiled a second time
    assert core._decode_fn._cache_size() == 1
    core.alloc.free(pages)
    # and the engine goes on from the cache the check left
    core.submit([1, 2, 3], max_tokens=4, rid="again")
    _, events = _drain(core)
    assert [e["token"] for e in events] == _alone(served, [1, 2, 3], 4)


def test_pipeline_counters_on_a_fixed_schedule(tiny_model):
    """Two lanes; a runs to four tokens, b stops at its second. Call 1
    prefills both and dispatches step 1; call 2 dispatches step 2 ahead
    and reads step 1 (b's stop token: its lane in step 2 is discarded);
    call 3 dispatches a's last step ahead; call 4 reads it with nothing
    behind."""
    cfg, model, params = tiny_model
    second = _alone((cfg, params), [4, 5, 6, 7], 2)[1]
    core = EngineCore(cfg, params, num_pages=32, page_size=4, max_batch=2)
    core.submit([1, 2, 3], max_tokens=4, rid="a")
    core.submit([4, 5, 6, 7], max_tokens=9, stop=(second,), rid="b")
    order, _ = _drain(core)
    assert order == [("b", FINISH_STOP), ("a", FINISH_LENGTH)]
    keys = ("steps", "decode_steps", "decode_lane_steps",
            "decode_steps_ahead", "pipeline_flushes",
            "discarded_lane_steps")
    assert [core.stats()[k] for k in keys] == [4, 3, 5, 2, 1, 1]
    # a request cancelled with its step in flight: the dispatch is
    # abandoned, not flushed
    core.submit([1, 2, 3], max_tokens=9, rid="c")
    core.step()
    core.step()
    core.cancel("c")
    assert [core.stats()[k] for k in keys] == [6, 5, 7, 3, 1, 2]
    assert not core.has_work


# ------------------------------------------------------ engine + stream
def test_engine_stream_replay_and_signals(tiny_model):
    """(Was `test_engine_polled_path_and_signals`: the same six tokens,
    replay and signals, read through subscriptions.)"""
    eng = LLMEngine(model="tiny", num_pages=32, page_size=8,
                    max_batch=4, seed=0)
    try:
        acc = eng.generate([1, 2, 3], max_tokens=6, rid="p")
        assert acc["rid"] == "p" and acc["attempt"] == 0
        out, last = read_stream(acc)
        assert len(out) == 6 and last["reason"] == FINISH_LENGTH
        assert last["inc"] == acc["incarnation"]
        # cursor replay: a second subscription at 0 gets the whole
        # prefix again (dup-safe), one from the middle gets the rest
        again, last = read_stream(acc)
        assert again == out and last["base"] == 0 and last["done"]
        assert read_stream(acc, cursor=4)[0] == out[4:]
        st = eng.engine_stats()
        assert st["queue_wait_p95"] >= 0.0
        assert tuple(st["stream"]) == tuple(acc["stream"])
        hook = eng.__serve_stats__()
        assert set(hook) >= {"queue_wait_p95", "outstanding_tokens"}
    finally:
        eng.close()


def test_engine_has_one_token_path_whatever_the_environment(monkeypatch):
    """The switch that chose a polled path is gone: its variable in the
    environment selects nothing, and there is nothing to poll."""
    from ray_tpu._private.config import CONFIG
    monkeypatch.setenv("RAY_TPU_LLM_STREAM", "0")
    CONFIG.reload()
    try:
        for gone in ("llm_stream", "llm_stream_wait_s"):
            with pytest.raises(AttributeError):
                getattr(CONFIG, gone)
        eng = LLMEngine(model="tiny", num_pages=32, page_size=8,
                        max_batch=2, seed=0)
        try:
            assert not hasattr(eng, "next_tokens")
            assert not hasattr(eng, "_cond")
            acc = eng.generate([1, 2, 3], max_tokens=3)
            assert acc["stream"] == eng._stream.addr
            assert len(read_stream(acc)[0]) == 3
        finally:
            eng.close()
    finally:
        monkeypatch.delenv("RAY_TPU_LLM_STREAM")
        CONFIG.reload()


def test_engine_push_stream_and_zombie_fence(tiny_model):
    from ray_tpu.serve.llm.stream import STREAM_STATS, stream_client
    eng = LLMEngine(model="tiny", num_pages=32, page_size=8,
                    max_batch=4, seed=0)
    try:
        cl = stream_client()
        acc = eng.generate([4, 5, 6], max_tokens=5, rid="push1")
        assert acc["stream"] is not None
        toks, last = read_stream(acc)
        assert len(toks) == 5 and last["reason"] == FINISH_LENGTH

        # wrong incarnation -> every frame fenced, nothing delivered
        z0 = STREAM_STATS["zombie_dropped"]
        eng.generate([4, 5, 6], max_tokens=3, rid="push2")
        sink2 = queue.Queue()
        assert cl.subscribe(acc["stream"], "push2", "deadbeef", 0, 0,
                            sink2)
        deadline = time.time() + 5
        while STREAM_STATS["zombie_dropped"] == z0 \
                and time.time() < deadline:
            time.sleep(0.02)
        assert STREAM_STATS["zombie_dropped"] > z0
        assert sink2.empty()

        # unknown rid -> terminal unknown frame (consumer fails over)
        sink3 = queue.Queue()
        assert cl.subscribe(acc["stream"], "ghost",
                            acc["incarnation"], 0, 0, sink3)
        m = sink3.get(timeout=5)
        assert m.get("unknown") and m["done"]
    finally:
        eng.close()


class _Remote:
    """`.remote(...)` that answers in place (with `ray_tpu.get` patched
    to hand its argument back)."""

    def __init__(self, fn):
        self.remote = fn


class _Replica:
    """What `TokenStream` sees of a replica actor: an id and
    `handle_request.remote(method, args, kwargs, stream)`."""

    def __init__(self, actor_id, engine=None):
        self._actor_id = actor_id
        self.calls = []

        def call(method, args, kwargs, _streaming):
            self.calls.append(method)
            if engine is not None:
                return getattr(engine, method)(*args, **kwargs)
            # a replica that accepts the generation and names no stream
            return {"rid": "lost", "attempt": kwargs["attempt"],
                    "incarnation": "none", "stream": None}
        self.handle_request = _Remote(call)


def test_replica_without_a_stream_address_is_failed_over(monkeypatch):
    """No transport is tried in the stream's place: the attempt fails as
    a refused subscription does, the replica cools down, owes nothing,
    and the next replica serves the whole generation."""
    import ray_tpu
    from ray_tpu.serve.llm import router
    monkeypatch.setattr(ray_tpu, "get", lambda x, timeout=None: x)
    eng = LLMEngine(model="tiny", num_pages=32, page_size=8,
                    max_batch=2, seed=0)
    try:
        mute, good = _Replica("mute"), _Replica("good", eng)
        ctrl = type("Ctrl", (), {})()
        ctrl.get_replicas = _Remote(lambda name: [mute, good])
        handle = router.LLMHandle("llm", controller=ctrl)
        s = handle.generate([1, 2, 3], max_tokens=5, timeout_s=20)
        assert not hasattr(s, "_push")
        assert len(s.tokens()) == 5 and s.finish_reason == FINISH_LENGTH
        assert mute.calls == ["generate"]       # asked once, never polled
        assert good.calls == ["generate"]
        assert s._replica is good and s.failovers == 0
        assert "mute" in handle._cooldown
        assert handle._depth.get("mute", 0) == 0
        assert handle._depth.get("good", 0) == 0
        # nobody left to ask: an error, not a wait on a path that is gone
        ctrl.get_replicas = _Remote(lambda name: [mute])
        handle._refresh(force=True)
        with pytest.raises(RuntimeError, match="generate failed"):
            handle.generate([1, 2, 3], max_tokens=2, timeout_s=5)
    finally:
        eng.close()


def test_engine_drain_marks_and_publishes(tiny_model):
    eng = LLMEngine(model="tiny", num_pages=32, page_size=8,
                    max_batch=2, seed=0)
    try:
        acc = eng.generate([1] * 20, max_tokens=40, rid="d")
        descs = eng.drain()
        assert [d["rid"] for d in descs] == ["d"]
        d = descs[0]
        # descriptor carries everything a survivor needs to re-prefill
        assert d["prompt"] == [1] * 20 and d["max_tokens"] == 40
        # a subscriber sees the terminal frame and fails over
        _, last = read_stream(acc)
        assert last["done"] and last["reason"] == "drained"
    finally:
        eng.close()


# ---------------------------------------------------------------- e2e
@pytest.mark.slow      # two replica groups: worker spawn + per-replica
                       # jit compile dominate (~1 min wall)
def test_llm_e2e_two_replicas_short_finishes_first(ray_cluster):
    from ray_tpu import serve
    from ray_tpu.serve import llm
    from ray_tpu.serve.llm.stream import STREAM_STATS
    try:
        handle = llm.serve_llm(name="llm-e2e", model="tiny",
                               num_replicas=2, num_pages=64,
                               page_size=8, max_batch=4)
        # both replicas' programs built before the race is timed: a cold
        # replica compiles for seconds, and which of two finishes first is
        # then the compiler's word (5 runs of 6 failed so at the parent,
        # six at once on a loaded box: PR 66)
        for warm in [handle.generate([9, 9, 9, 9], max_tokens=2,
                                     timeout_s=120) for _ in range(2)]:
            assert len(warm.tokens()) == 2
        t_in0 = STREAM_STATS["tokens_in"]
        long_s = handle.generate([1, 2, 3, 4], max_tokens=48,
                                 timeout_s=120)
        short_s = handle.generate([5, 6, 7, 8], max_tokens=4,
                                  timeout_s=120)
        done_at = {}
        results = {}

        def consume(name, s):
            results[name] = s.tokens()
            done_at[name] = time.monotonic()

        th = [threading.Thread(target=consume, args=("long", long_s)),
              threading.Thread(target=consume, args=("short", short_s))]
        for t in th:
            t.start()
        for t in th:
            t.join(timeout=180)
        assert len(results["short"]) == 4
        assert len(results["long"]) == 48
        assert done_at["short"] < done_at["long"]
        # the push transport carried the tokens
        assert STREAM_STATS["tokens_in"] - t_in0 >= 52
        st = handle.stats()
        assert len(st) >= 2          # one engine_stats dict per replica
    finally:
        from ray_tpu import serve as _s
        _s.shutdown()
