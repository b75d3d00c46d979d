"""The class whose attention reads the positions a learned indexer chooses
(`models.SparseMLAMoE`, `ops/sparse_attention.py`) against
`benchmarks/models/glm_moe_dsa.py`'s plain reference and against itself:
prefill then decode across `index_topk` (below, at and above it, a bucket
with padding, a lane that crosses it while decoding), the chosen sets
against the reference's, a lane under `index_topk` against the dense latent
attention, the two pools under one page id, the kernels under the Pallas
interpreter, the shares of an expert layer adding up, the engine's counters.
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import modelcfg                      # noqa: E402
from benchmarks.harness.reference import _ident, _rms, rel_rms  # noqa: E402
from benchmarks.harness.weights import make_weights          # noqa: E402
from ray_tpu.models import (MODELS, SparseMLAMoE,            # noqa: E402
                            SparseMLAMoEConfig, build_model, model_config)
from ray_tpu.models.moe import dropless_moe_ffn              # noqa: E402
from ray_tpu.models.sparse_mla_moe import (                  # noqa: E402
    DSA_COUNTS, SparseLatentAttention, tiny_sparse_mla_moe)
from ray_tpu.ops import paged_attention as paged             # noqa: E402
from ray_tpu.ops import sparse_attention as sparse           # noqa: E402
from ray_tpu.ops.rope import rope_cos_sin                    # noqa: E402
from ray_tpu.serve.llm.engine import EngineCore, _bucket     # noqa: E402
from ray_tpu.serve.llm.kv_cache import PageAllocator         # noqa: E402

CONFIG = "glm-5-1chip"
PAGE, TOPK, CONTEXT = 16, 32, 256


def _ref(**sizes):
    """(model module, Sizes, seeded float32 weights, the program's model)
    at `tiny(cfg)` with `index_topk` 32, `sizes` changing other keys."""
    cfg = modelcfg.load_config(CONFIG)
    mod = modelcfg.load_model(cfg)
    small = {**mod.tiny(cfg), "index_topk": TOPK, **sizes}
    sz = mod.sizes(small)
    params = make_weights(mod.weight_shapes(sz), 7, dtype=jnp.float32)
    pc = mod.program_config(small, CONTEXT, dtype="float32",
                            param_dtype="float32")
    return mod, sz, params, build_model(pc)


@pytest.fixture(scope="module")
def tiny_ref():
    return _ref()


def _serve(model, params, toks, p, steps, lane=2, lanes=4, first_page=3):
    """Logits of prefill (bucketed, padded) then `steps` decode steps of
    one sequence in lane `lane`; returns (rows, cache, its page table)."""
    cache = model.init_cache(64, PAGE)
    table = np.full((CONTEXT // PAGE,), -1, np.int32)
    n = -(-(p + steps) // PAGE)
    table[:n] = np.arange(n) + first_page
    s_pad = _bucket(p, hi=CONTEXT)
    padded = np.zeros((s_pad,), np.int32)
    padded[:p] = toks[:p]
    logits, cache = jax.jit(model.prefill, static_argnums=(5,))(
        params, jnp.asarray(padded), jnp.int32(p), jnp.asarray(table),
        cache, PAGE)
    rows = [logits]
    step = jax.jit(model.decode_step, static_argnums=(6,))
    for k in range(steps):
        tokens = np.zeros((lanes,), np.int32)
        positions = np.zeros((lanes,), np.int32)
        tables = np.full((lanes, CONTEXT // PAGE), -1, np.int32)
        active = np.zeros((lanes,), bool)
        tokens[lane], positions[lane] = toks[p + k], p + k
        tables[lane], active[lane] = table, True
        logits, cache = step(params, cache, jnp.asarray(tokens),
                             jnp.asarray(positions), jnp.asarray(tables),
                             jnp.asarray(active), PAGE)
        rows.append(logits[lane])
    return jnp.stack(rows), cache, table


# ------------------------------------------- against the plain reference
@pytest.mark.parametrize("p,steps", [
    (20, 8),        # below index_topk throughout: dense causal MLA
    (24, 20),       # a lane that crosses index_topk while decoding
    (32, 6),        # a prompt of exactly index_topk
    (33, 6),        # one past it: a bucket of 64 with padding, sparse
    (100, 12),      # a bucket of 128: three sets of four positions dropped
])
def test_prefill_then_decode_match_the_reference(tiny_ref, p, steps):
    mod, sz, params, model = tiny_ref
    toks = np.random.default_rng(p).integers(0, sz.vocab, p + steps).astype(
        np.int32)
    got, cache, _ = _serve(model, params, toks, p, steps)
    full = np.zeros((CONTEXT,), np.int32)
    full[:p + steps] = toks
    want = mod.reference_rows(sz, params, jnp.asarray(full),
                              jnp.int32(p - 1), steps + 1)
    assert rel_rms(got, want) < 1e-5
    # and the selection is what the logits stand on: past index_topk the
    # reference that attends to every position is another function
    dense = mod.reference_rows(sz, params, jnp.asarray(full),
                               jnp.int32(p - 1), steps + 1, False, True)
    if p + steps > TOPK + 1:
        assert rel_rms(got, dense) > 0.02
    else:
        assert rel_rms(got, dense) < 1e-5
    # the last step's counts: every layer scored what the lane holds and
    # read index_topk of it at most
    stats = {k: int(v) for k, v in model.step_stats(cache).items()}
    held = p + steps
    assert stats["dsa_positions_scored"] == sz.layers * held
    assert stats["dsa_positions_selected"] == sz.layers * min(held, TOPK)
    assert stats["dsa_lanes_past_topk"] == sz.layers * (held > TOPK)


def test_apply_is_the_reference_past_index_topk(tiny_ref):
    mod, sz, params, model = tiny_ref
    toks = np.random.default_rng(1).integers(0, sz.vocab, 128).astype(
        np.int32)
    got = jax.jit(model.apply)(params, jnp.asarray(toks[None]))[0]
    assert rel_rms(got, mod.logits_fn(sz, params, jnp.asarray(toks))) < 1e-5


def test_the_fp8_control_is_told_from_the_reference(tiny_ref):
    mod, sz, params, _ = tiny_ref
    toks = jnp.asarray(np.random.default_rng(2).integers(0, sz.vocab, 128),
                       jnp.int32)
    want = mod.reference_rows(sz, params, toks, jnp.int32(90), 32)
    control = mod.reference_rows(sz, params, toks, jnp.int32(90), 32, True)
    assert 0.01 < rel_rms(control, want) < 1.0


# ------------------------------------------------------ the chosen sets
def _index_inputs(tiny_ref, s, seed=0):
    """One layer's indexer on `s` tokens, from the program and from the
    reference: ((q_idx, w, k_idx) of each)."""
    mod, sz, params, model = tiny_ref
    c = model.config
    layer = params["layers"][1]
    toks = np.random.default_rng(seed).integers(0, sz.vocab, s)
    x = params["embed"][toks]
    h = model._norm(x[None], layer["attn_norm"])
    cos, sin = rope_cos_sin(jnp.arange(s)[None], c.qk_rope_head_dim,
                            c.rope_theta)
    c_q, _ = model._q_latent(layer, h)
    q_idx, w = model._index_query(layer, h, c_q, cos, sin)
    k_idx = model._index_key(layer, h, cos, sin)
    hr = _rms(x, layer["attn_norm"], sz.norm_eps)
    cqr = _rms(hr @ layer["wq_a"], layer["q_norm"], sz.norm_eps)
    return (q_idx[0], w[0], k_idx[0]), mod.index_parts(
        sz, hr, cqr, layer, jnp.arange(s))


def test_a_prefills_sets_are_the_references(tiny_ref):
    mod, sz, _, _ = tiny_ref
    (q, w, k), (qr, kr, wr) = _index_inputs(tiny_ref, 128)
    assert float(jnp.abs(q - qr).max()) < 1e-5
    assert float(jnp.abs(k - kr).max()) < 1e-5
    keep = np.asarray(sparse.prefill_keep_mask(q, w, k, TOPK)) != 0
    want = np.asarray(mod.selected_mask(sz, qr, kr, wr))
    assert (keep == want).all()
    assert (keep.sum(axis=1) == np.minimum(np.arange(128) + 1, TOPK)).all()
    assert not np.triu(keep, 1).any()


def test_a_decode_steps_sets_are_the_references(tiny_ref):
    """Lanes of unlike lengths over pages in any order: the positions
    `select_topk` takes from the paged scores are the reference's row."""
    mod, sz, _, model = tiny_ref
    s = 128
    (q, w, k), (qr, kr, wr) = _index_inputs(tiny_ref, s, seed=3)
    want = np.asarray(mod.selected_mask(sz, qr, kr, wr))
    order = np.random.default_rng(0).permutation(40)
    lengths = np.array([128, 77, 0, 20], np.int32)
    tables = np.full((4, CONTEXT // PAGE), -1, np.int32)
    pool = jnp.zeros((2, 41, PAGE, k.shape[-1]))
    for lane, n in enumerate(lengths):
        if not n:
            continue
        pages = order[lane * 8:lane * 8 + -(-int(n) // PAGE)]
        tables[lane, :len(pages)] = pages
        rows = jnp.pad(k[:n], ((0, len(pages) * PAGE - n), (0, 0)))
        pool = pool.at[1, pages].set(rows.reshape(len(pages), PAGE, -1))
    t = np.maximum(lengths - 1, 0)
    scores = sparse.index_scores_paged(q[t], w[t], pool, 1,
                                       jnp.asarray(tables),
                                       jnp.asarray(lengths))
    positions, chosen = sparse.select_topk(scores, TOPK)
    for lane, n in enumerate(lengths):
        got = set(np.asarray(positions[lane])[np.asarray(chosen[lane])])
        assert got == (set(np.flatnonzero(want[n - 1])) if n else set())


def test_up_to_index_topk_positions_a_lane_reads_what_the_dense_kernel_reads():
    """Tables that could pass `index_topk` (so the step is traced sparse),
    lanes that hold at most `index_topk` positions: every position is
    chosen and the result is `mla_paged_decode_attention`'s."""
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    lanes, heads, width, latent, topk = 4, 8, 256, 128, 48
    pool = jax.random.normal(ks[0], (2, 32, PAGE, width))
    idx_pool = jax.random.normal(ks[1], (2, 32, PAGE, 32))
    q = jax.random.normal(ks[2], (lanes, heads, width))
    q_idx = jax.random.normal(ks[3], (lanes, 16, 32))
    w = jax.random.normal(ks[4], (lanes, 16))
    lengths = jnp.asarray([48, 17, 0, 33], jnp.int32)
    tables = jnp.asarray(np.random.default_rng(1).permutation(32).reshape(
        4, 8).astype(np.int32))
    tables = jnp.where(jnp.arange(8)[None] * PAGE < lengths[:, None],
                       tables, -1)
    scores = sparse.index_scores_paged(q_idx, w, idx_pool, 1, tables,
                                       lengths)
    positions, chosen = sparse.select_topk(scores, topk)
    assert (np.asarray(chosen).sum(axis=1) == np.asarray(lengths)).all()
    got = sparse.mla_selected_attention(q, pool, 1, tables, positions,
                                        chosen, latent, 0.2)
    for dense in (paged.mla_paged_decode_attention,
                  paged.mla_paged_decode_attention_kernel):
        want = dense(q, pool, 1, tables, lengths, latent, 0.2)
        assert float(jnp.abs(got - want).max()) < 2e-5
    assert not np.asarray(got[2]).any()


def _interpreted(monkeypatch, index_pages=4, attend_pages=8):
    """The step's two walks as kernels under the Pallas interpreter,
    whatever the shapes and the platform."""
    monkeypatch.setattr(sparse, "INDEX_WALK_PAGES", index_pages)
    monkeypatch.setattr(sparse, "ATTEND_WALK_PAGES", attend_pages)
    monkeypatch.setattr(sparse, "step_uses_kernels", lambda *a: True)
    index, attend = sparse._paged_index_call, sparse._paged_attend_call
    monkeypatch.setattr(sparse, "_paged_index_call",
                        lambda *a, **run: index(*a[:-1], True, **run))
    monkeypatch.setattr(sparse, "_paged_attend_call",
                        lambda *a, **run: attend(*a[:-1], True, **run))


def test_the_steps_kernels_choose_and_read_what_the_gathers_do(monkeypatch):
    """Lanes of unlike lengths (none, a part of a page, blocks and a part,
    the whole table) over pages in any order: the walk over live index
    pages scores what the gather scores, the threshold keeps what `top_k`
    takes, and the walk that reads every live row and keeps the chosen
    gives what the gather of the chosen rows gives."""
    _interpreted(monkeypatch)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    lanes, heads, width, latent, topk = 4, 6, 256, 128, 48
    pool = jax.random.normal(ks[0], (2, 64, PAGE, width))
    idx_pool = jax.random.normal(ks[1], (2, 64, PAGE, 128))
    q = jax.random.normal(ks[2], (lanes, heads, width))
    q_idx = jax.random.normal(ks[3], (lanes, 16, 128))
    w = jax.random.normal(ks[4], (lanes, 16))
    lengths = jnp.asarray([200, 17, 0, 256], jnp.int32)
    tables = jnp.asarray(np.random.default_rng(1).permutation(64).reshape(
        4, 16).astype(np.int32))
    tables = jnp.where(jnp.arange(16)[None] * PAGE < lengths[:, None],
                       tables, -1)
    args = (q_idx, w, idx_pool, 1, tables, lengths)
    plain = sparse.index_scores_paged(*args)
    walked = sparse._paged_index_call(*args, True)
    assert (np.isfinite(plain) == np.isfinite(walked)).all()
    assert float(jnp.abs(jnp.where(jnp.isfinite(plain), plain - walked,
                                   0)).max()) < 1e-4
    (positions, chosen), n = sparse.choose_paged(*args, topk, False)
    keep, m = sparse.choose_paged(*args, topk, True)
    assert list(np.asarray(n)) == list(np.asarray(m)) == [48, 17, 0, 48]
    for lane in range(lanes):
        assert set(np.flatnonzero(keep[lane])) == set(
            np.asarray(positions[lane])[np.asarray(chosen[lane])])
    rest = (q, pool, 1, tables, lengths)
    got = sparse.attend_chosen(*rest, keep, latent, 0.2, True)
    want = sparse.attend_chosen(*rest, (positions, chosen), latent, 0.2,
                                False)
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert not np.asarray(got[2]).any()


def _run_tables(lengths, max_pages, num_pages, run, seed=1, fixed=0):
    """Tables of lanes that hold `lengths` positions, their pages handed
    out by the allocator in runs of `run`, a page (so a run) at a time in
    any order of the lanes: the runs of a lane lie anywhere in the pool,
    behind its `fixed` pages of the fixed class, which come one by one in
    that order too."""
    alloc = PageAllocator(num_pages, fixed=fixed, sequences=len(lengths),
                          run=run)
    rng = np.random.default_rng(seed)
    need = [-(-int(n) // PAGE) for n in lengths]
    held = [[] for _ in lengths]
    spare = alloc.alloc(run)            # so that no lane starts at page 0
    while any(len(h) < n for h, n in zip(held, need)):
        lane = int(rng.integers(len(held)))
        if len(held[lane]) < need[lane]:
            held[lane] += alloc.alloc(1, held=len(held[lane]))
    alloc.free(spare)
    tables = np.full((len(held), max_pages), -1, np.int32)
    for lane, pages in enumerate(held):
        tables[lane, :len(pages)] = pages
    return jnp.asarray(tables)


@pytest.mark.parametrize("run", [4, 8])
def test_the_steps_kernels_copy_a_run_at_a_time(monkeypatch, run):
    """On tables the allocator laid out in runs, a walk that brings a run
    a copy scores and reads what the plain forms do, and to the bit what
    the walk a page a copy does: a lane shorter than one run, one whose
    last run is partly live, one at `max_pages`, one that holds nothing;
    the pages of a last run that no position has reached are the lane's
    own, whatever they hold, and masked."""
    _interpreted(monkeypatch, index_pages=16, attend_pages=8)
    ks = jax.random.split(jax.random.PRNGKey(run), 5)
    lanes, heads, width, latent, topk = 4, 6, 256, 128, 48
    pool = jax.random.normal(ks[0], (2, 64, PAGE, width))
    idx_pool = jax.random.normal(ks[1], (2, 64, PAGE, 128))
    q = jax.random.normal(ks[2], (lanes, heads, width))
    q_idx = jax.random.normal(ks[3], (lanes, 16, 128))
    w = jax.random.normal(ks[4], (lanes, 16))
    lengths = jnp.asarray([2 * PAGE + 3, (run + 2) * PAGE - 5, 0,
                           16 * PAGE], jnp.int32)
    tables = _run_tables(lengths, 16, 64, run)
    held = np.asarray(tables >= 0).sum(axis=1)
    assert list(held) == [run, 2 * run, 0, 16]      # whole runs, spare pages
    firsts = np.asarray(tables)[:, ::run]
    assert not (firsts[firsts >= 0] % run).any()
    args = (q_idx, w, idx_pool, 1, tables, lengths)
    plain = sparse.index_scores_paged(*args)
    by_page = sparse._paged_index_call(*args, True)
    by_run = sparse._paged_index_call(*args, True, run=run)
    assert (np.asarray(by_run) == np.asarray(by_page)).all()
    assert (np.isfinite(plain) == np.isfinite(by_run)).all()
    assert float(jnp.abs(jnp.where(jnp.isfinite(plain), plain - by_run,
                                   0)).max()) < 1e-4
    (positions, chosen), n = sparse.choose_paged(*args, topk, False)
    keep, m = sparse.choose_paged(*args, topk, True, run=run)
    assert list(np.asarray(n)) == list(np.asarray(m)) == [35, 48, 0, 48]
    for lane in range(lanes):
        assert set(np.flatnonzero(keep[lane])) == set(
            np.asarray(positions[lane])[np.asarray(chosen[lane])])
    rest = (q, pool, 1, tables, lengths)
    got = sparse.attend_chosen(*rest, keep, latent, 0.2, True, run=run)
    paged = sparse.attend_chosen(*rest, keep, latent, 0.2, True)
    want = sparse.attend_chosen(*rest, (positions, chosen), latent, 0.2,
                                False)
    assert (np.asarray(got) == np.asarray(paged)).all()
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert not np.asarray(got[2]).any()
    # a table that is not whole runs of a block is refused, not misread
    with pytest.raises(ValueError, match="whole runs"):
        sparse._paged_attend_call(*rest, keep, latent, 0.2, True, run=16)


@pytest.mark.parametrize("fixed,run", [
    (fixed, run) for fixed in (1, 34) for run in (1, 4, 8)])
def test_the_steps_kernels_copy_runs_behind_a_tables_fixed_entries(
        monkeypatch, fixed, run):
    """Tables as a class that keeps a ring or a slot has them (PR 66):
    `fixed` entries of the fixed class, handed out page by page in no
    order, then whole runs. The two walks, told `fixed`, score and read
    what the plain forms do: a lane that holds nothing, one inside its
    fixed entries, one that holds exactly those, one a run's first page
    alone, one that ends inside a run, one that crosses the walks' blocks
    and one at the table's end; a page a copy up to entry `fixed`, a run a
    copy from there on, whatever the pages ahead of a lane's length hold."""
    _interpreted(monkeypatch, index_pages=16, attend_pages=8)
    ks = jax.random.split(jax.random.PRNGKey(run), 5)
    table = paged.run_table_pages(48, fixed, run)
    assert table == fixed + -(-(48 - fixed) // run) * run
    lengths = jnp.asarray([
        0, fixed * PAGE - 3, fixed * PAGE, fixed * PAGE + 1,
        (fixed + run) * PAGE + 3, 43 * PAGE - 1, table * PAGE], jnp.int32)
    lanes, heads, width, latent, topk = len(lengths), 6, 256, 128, 48
    pages = lanes * (table + run) + run
    pool = jax.random.normal(ks[0], (2, pages, PAGE, width))
    idx_pool = jax.random.normal(ks[1], (2, pages, PAGE, 128))
    q = jax.random.normal(ks[2], (lanes, heads, width))
    q_idx = jax.random.normal(ks[3], (lanes, 16, 128))
    w = jax.random.normal(ks[4], (lanes, 16))
    tables = _run_tables(lengths, table, pages, run, fixed=fixed)
    t = np.asarray(tables)
    assert (t[:, :fixed][t[:, :fixed] >= 0] < lanes * fixed).all()
    firsts = t[:, fixed::run]
    assert (firsts[firsts >= 0] >= lanes * fixed).all()
    assert not (firsts[firsts >= 0] % run).any()
    args = (q_idx, w, idx_pool, 1, tables, lengths)
    plain = sparse.index_scores_paged(*args)
    walked = sparse._paged_index_call(*args, True, run=run, fixed=fixed)
    assert walked.shape == plain.shape == (lanes, table * PAGE)
    assert (np.isfinite(plain) == np.isfinite(walked)).all()
    assert float(jnp.abs(jnp.where(jnp.isfinite(plain), plain - walked,
                                   0)).max()) < 1e-4
    (positions, chosen), n = sparse.choose_paged(*args, topk, False)
    keep, m = sparse.choose_paged(*args, topk, True, run=run, fixed=fixed)
    assert list(np.asarray(n)) == list(np.asarray(m)) == [
        min(int(length), topk) for length in lengths]
    for lane in range(lanes):
        assert set(np.flatnonzero(keep[lane])) == set(
            np.asarray(positions[lane])[np.asarray(chosen[lane])])
    rest = (q, pool, 1, tables, lengths)
    got = sparse.attend_chosen(*rest, keep, latent, 0.2, True, run=run,
                               fixed=fixed)
    want = sparse.attend_chosen(*rest, (positions, chosen), latent, 0.2,
                                False)
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert not np.asarray(got[0]).any()
    if run > 1:     # a table that is no whole runs behind its fixed entries
        with pytest.raises(ValueError, match="whole runs"):
            sparse._paged_attend_call(*rest, keep, latent, 0.2, True,
                                      run=run, fixed=fixed + 1)


# the two walks' texts as the parent (PR 65) traced them at these shapes
# (4 lanes, tables of 64 pages of 16, 16 index heads; sha256 of
# `str(jaxpr)` without source lines): a walk told of no fixed entries, or
# walking a page a copy, is that program to the byte
WALK_TEXTS = {("index", 1): "234be3dd4db38af6",
              ("index", 8): "7501b1165cdae10b",
              ("attend", 1): "04aadd342fe1ff4f",
              ("attend", 8): "dff507a1a917eea7"}


@pytest.mark.parametrize("walk,run,fixed", [
    *((walk, run, 0) for walk, run in sorted(WALK_TEXTS)),
    ("index", 1, 34), ("attend", 1, 34), ("index", 1, 1), ("attend", 1, 1)])
def test_a_walk_without_fixed_entries_traces_the_parents_text(walk, run,
                                                              fixed):
    import hashlib
    import re
    S, i32, f32 = jax.ShapeDtypeStruct, jnp.int32, jnp.float32
    tables = (S((1,), i32), S((4, 64), i32), S((4,), i32))
    if walk == "index":
        traced = sparse._paged_index_call.trace(
            S((4, 16, 128), f32), S((4, 16), f32), S((2, 300, 16, 128), f32),
            *tables, interpret=True, run=run, fixed=fixed)
    else:
        traced = sparse._paged_attend_call.trace(
            S((4, 6, 256), f32), S((2, 300, 16, 256), f32), *tables,
            S((4, 64 * 16), jnp.bool_), latent=128, sm_scale=0.1,
            interpret=True, run=run, fixed=fixed)
    text = re.sub(r" at (0x[0-9a-f]+|[^\s\]]+:\d+)", "", str(traced.jaxpr))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == WALK_TEXTS[
        walk, run]


def test_the_run_is_the_class_answer_from_shapes(monkeypatch):
    """`page_run`: 1 where a step runs no walk kernel (a context that
    cannot pass `index_topk`; shapes that do not tile, so every CPU
    engine), else as many pages as make a copy of the smaller pool's page
    `RUN_COPY_BYTES` long, cut to a divisor of the table and the blocks."""
    assert sparse.walk_run_pages(16 * 128 * 2, 1024) == 8      # GLM-5's
    assert sparse.walk_run_pages(16 * 128 * 2, 1000) == 8
    assert sparse.walk_run_pages(16 * 128 * 2, 36) == 4
    assert sparse.walk_run_pages(16 * 128 * 2, 7) == 1
    assert sparse.walk_run_pages(16 * 128, 1024) == 16
    assert sparse.walk_run_pages(64 << 10, 1024) == 1
    assert sparse.walk_run_pages(5000, 1024) == 8              # 7 made 8
    cfg = tiny_sparse_mla_moe(index_topk=16)
    assert SparseMLAMoE(cfg).page_run(8, 16) == 1              # no kernel
    monkeypatch.setattr(sparse, "step_uses_kernels", lambda *a: True)
    assert SparseMLAMoE(cfg).page_run(8, 16) == 16  # 1 KB a page; 16 | 16
    assert SparseMLAMoE(cfg).page_run(8, 12) == 4
    under = tiny_sparse_mla_moe(index_topk=128)      # max_seq_len 128
    assert SparseMLAMoE(under).page_run(8, 16) == 1
    # behind a fixed class's entries the run is sized on the entries that
    # grow and the table is made whole runs of it (notes12k: 34 + 992)
    assert sparse.walk_run_pages(16 * 128 * 2, 1024, 34) == 8
    assert sparse.walk_run_pages(16 * 128 * 2, 1026, 34) == 8
    assert paged.run_table_pages(1024, 34, 8) == 1026
    assert sparse.walk_run_pages(16 * 128 * 2, 36, 34) == 2    # 2 entries
    assert sparse.walk_run_pages(16 * 128 * 2, 40, 34) == 8    # 6 -> 8
    assert paged.run_table_pages(40, 34, 8) == 42
    assert sparse.walk_run_pages(16 * 128 * 2, 42, 34) == 8
    from ray_tpu.models import MLAMoE, mla_moe
    assert MLAMoE(mla_moe.tiny_mla_moe()).page_run(8, 16) == 1


@pytest.mark.parametrize("p,steps", [(24, 20), (100, 12)])
def test_served_path_with_the_steps_kernels_under_the_interpreter(
        tiny_ref, monkeypatch, p, steps):
    _interpreted(monkeypatch)
    mod, sz, params, model = tiny_ref
    toks = np.random.default_rng(p).integers(0, sz.vocab, p + steps).astype(
        np.int32)
    got, cache, _ = _serve(model, params, toks, p, steps)
    full = np.zeros((CONTEXT,), np.int32)
    full[:p + steps] = toks
    want = mod.reference_rows(sz, params, jnp.asarray(full),
                              jnp.int32(p - 1), steps + 1)
    assert rel_rms(got, want) < 1e-5
    stats = {k: int(v) for k, v in model.step_stats(cache).items()}
    assert stats["dsa_positions_selected"] == sz.layers * min(p + steps,
                                                              TOPK)


# --------------------------------------------------------- the two pools
def test_both_pools_are_written_under_the_same_page_ids(tiny_ref):
    """A prefill writes whole pages and a decode step one row, of the
    latent pool and of the index pool alike, at the pages the table names
    and nowhere else."""
    _, sz, params, model = tiny_ref
    p, steps = 40, 9
    toks = np.random.default_rng(5).integers(0, sz.vocab, p + steps).astype(
        np.int32)
    _, cache, table = _serve(model, params, toks, p, steps, first_page=11)
    kv, idx = np.asarray(cache["kv"]), np.asarray(cache["idx"])
    assert kv.shape[:3] == idx.shape[:3] == (sz.layers, 64, PAGE)
    assert idx.shape[3] == sz.index_dim
    written = lambda pool: {int(i) for i in np.flatnonzero(     # noqa: E731
        np.abs(pool).sum(axis=(0, 2, 3)))}
    pages = {int(t) for t in table if t >= 0}
    assert written(kv) == written(idx) == pages == set(range(11, 15))
    # position by position: a row of either pool holds numbers exactly
    # where the sequence has a position
    held = np.zeros((64 * PAGE,), bool)
    for j in range(p + steps):
        held[table[j // PAGE] * PAGE + j % PAGE] = True
    for pool in (kv, idx):
        rows = np.abs(pool).sum(axis=3).reshape(sz.layers, -1) > 0
        assert (rows == held[None]).all()
    # and the engine's price of a page is both pools'
    c = model.config
    assert model.cache_page_bytes(PAGE) == sz.layers * PAGE * 4 * (
        c.row_width + c.index_head_dim)
    assert model.index_page_bytes(PAGE) == idx[:, 0].nbytes


# ------------------------------------------------------------ the kernels
def test_the_index_score_kernel_and_the_masked_flash_under_the_interpreter(
        monkeypatch):
    monkeypatch.setattr(sparse, "SELECT_ROWS", 256)
    monkeypatch.setattr(sparse, "INDEX_BLOCKS", (128, 128))
    monkeypatch.setattr(sparse, "FLASH_BLOCKS", (128, 128))
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    s, heads, width, topk = 512, 32, 128, 100
    q = jax.random.normal(ks[0], (s, heads, width))
    k = jax.random.normal(ks[1], (s, width))
    w = jax.random.normal(ks[2], (s, heads))
    plain = sparse.prefill_keep_mask(q, w, k, topk, kernel=False)
    tiled = sparse.prefill_keep_mask(q, w, k, topk, kernel=True,
                                     interpret=True)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)),
                       sparse.index_scores_reference(q, w, k), -jnp.inf)
    _, idx = jax.lax.top_k(scores, topk)
    want = np.zeros((s, s), np.int8)
    for t in range(s):
        want[t, np.asarray(idx[t, :min(topk, t + 1)])] = 1
    assert (np.asarray(plain) == want).all()
    assert (np.asarray(tiled) == want).all()
    qq = jax.random.normal(ks[3], (2, s, 128))
    kk = jax.random.normal(ks[4], (2, s, 128))
    vv = jax.random.normal(ks[5], (2, s, 256))
    a = sparse.masked_attention_reference(qq, kk, vv, plain, 0.1)
    b = sparse.masked_flash_attention_kernel(qq, kk, vv, plain, 0.1)
    assert float(jnp.abs(a - b).max()) < 1e-5


def test_a_threshold_tie_keeps_every_tied_key():
    """What `prefill_keep_mask` documents: equal scores at the threshold
    are all kept, where `top_k` would keep the lower positions."""
    scores = jnp.asarray([[3.0, 1.0, 1.0, 1.0, 0.0, 2.0, -1.0, 1.0]])
    keep = np.asarray(sparse.keep_rows(scores, 7, 3))[0]
    assert list(np.flatnonzero(keep)) == [0, 1, 2, 3, 5, 7]
    keep = np.asarray(sparse.keep_rows(scores, 7, 2))[0]
    assert list(np.flatnonzero(keep)) == [0, 5]
    # causal: row 2 of a block that starts at 0 sees three keys
    keep = np.asarray(sparse.keep_rows(jnp.tile(scores, (3, 1)), 0, 2))
    assert [list(np.flatnonzero(r)) for r in keep] == [[0], [0, 1], [0, 1, 2]]


# --------------------------------------------------- the shares add up
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(tiny_ref):
    """Two chips share a layer of 8 experts, 4 each (as 16 share GLM-5's
    256): the held experts' parts of both plus the shared expert counted
    once are the uncut layer's feed-forward, in the reference and in the
    program."""
    mod, sz, _, _ = tiny_ref
    whole = dataclasses.replace(sz, first_held=0, held=sz.experts)
    layer = make_weights(mod.weight_shapes(whole)["layers"][1], 13,
                         dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(4), (40, sz.d_model))
    shared = mod.shared_part(u, layer, _ident)
    uncut = mod.routed_part(whole, u, layer, _ident) + shared
    ref_parts, prog_parts, pairs = [], [], 0
    for first in range(0, sz.experts, 4):
        mine = {**layer, **{k: layer[k][first:first + 4]
                            for k in ("moe_gate", "moe_up", "moe_down")}}
        ref_parts.append(mod.routed_part(sz, u, mine, _ident, first))
        y, counts = dropless_moe_ffn(
            u, mine["router"], mine["router_bias"], mine["moe_gate"],
            mine["moe_up"], mine["moe_down"], top_k=sz.top_k,
            norm_topk_prob=sz.norm_topk, scale=sz.route_scale,
            held=(first, 4))
        prog_parts.append(y)
        pairs += int(counts["pairs"])
        assert int(counts["pairs"]) + int(counts["away_pairs"]) \
            == 40 * sz.top_k
    assert rel_rms(sum(ref_parts) + shared, uncut) < 1e-5
    assert rel_rms(sum(prog_parts) + shared, uncut) < 1e-5
    assert pairs == 40 * sz.top_k       # every pair is one share's
    assert rel_rms(ref_parts[0] + shared, uncut) > 0.1


def test_a_long_prefill_routes_its_tokens_block_by_block(tiny_ref,
                                                         monkeypatch):
    from ray_tpu.models import sparse_mla_moe as module
    _, sz, params, model = tiny_ref
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(1), (64, sz.d_model))
    valid = jnp.arange(64) < 50
    want, counts = model._ffn(layer, x, valid)
    monkeypatch.setattr(module, "FFN_ROWS", 16)
    got, blocked = model._ffn(layer, x, valid)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert int(blocked["pairs"]) == int(counts["pairs"])
    assert (np.asarray(blocked["load"]) == np.asarray(counts["load"])).all()


# ------------------------------------------------------------ the engine
def _greedy(model, params, prompt, n, pad=64):
    apply = jax.jit(model.apply)
    toks = list(prompt)
    for _ in range(n):
        padded = jnp.zeros((1, pad), jnp.int32).at[0, :len(toks)].set(
            jnp.asarray(toks, jnp.int32))
        toks.append(int(apply(params, padded)[0, len(toks) - 1].argmax()))
    return toks[len(prompt):]


def test_engine_core_serves_it_and_counts_what_it_chose():
    cfg = tiny_sparse_mla_moe(index_topk=16)
    model = SparseMLAMoE(cfg)
    params = model.init(jax.random.PRNGKey(0))
    core = EngineCore(cfg, params, num_pages=12, page_size=8, max_batch=3)
    assert isinstance(core.model, SparseMLAMoE)
    rng = np.random.default_rng(0)
    prompts = {"a": rng.integers(0, 256, 21).tolist(),      # past topk
               "b": [5, 6, 7],                              # far under it
               "c": rng.integers(0, 256, 12).tolist()}      # crosses it
    wanted = {"a": 9, "b": 8, "c": 10}
    for rid, n in wanted.items():
        core.submit(prompts[rid], max_tokens=n, rid=rid)
    got = {rid: [] for rid in prompts}
    for _ in range(200):
        if not core.has_work:
            break
        for ev in core.step():
            got[ev["rid"]].append(ev["token"])
    for rid, n in wanted.items():
        assert got[rid] == _greedy(model, params, prompts[rid], n), rid
    c = core.counters
    assert set(DSA_COUNTS) <= set(c)
    assert c["dsa_positions_scored"] == c["kv_positions_live"] * cfg.n_layers
    assert 0 < c["dsa_positions_selected"] < c["dsa_positions_scored"]
    assert c["dsa_lanes_past_topk"] > 0
    assert (c["moe_pairs"] + c["moe_away_pairs"]
            == c["decode_lane_steps"] * cfg.num_experts_per_tok
            * cfg.n_moe_layers)
    st = core.device_stats()
    assert st["decode_attention"] == "einsum"
    # a latent row of 128 numbers and an index key of 32, float32
    assert st["cache_bytes_per_position"] == cfg.n_layers * (128 + 32) * 4
    assert np.asarray(st["moe_load"]).shape == (cfg.n_moe_layers, 4)


def _serve_in_runs(monkeypatch, cfg, params, run,
                   mixer=SparseLatentAttention):
    """An engine whose step runs its walk kernels (interpreted) over a
    pool too small for its three lanes, pages in runs of `run` (None: the
    class's own answer; else what its `mixer` is made to answer):
    admission, growth across a run's end, an eviction and its
    re-admission, a cancel. Returns (tokens by request, the engine), every
    lane's table checked after every step."""
    if run is not None:
        monkeypatch.setattr(mixer, "page_run", lambda *a: run)
    core = EngineCore(cfg, params, num_pages=17, page_size=8, max_batch=3)
    run = core.alloc.run
    rng = np.random.default_rng(0)
    prompts = {"a": rng.integers(0, 256, 21).tolist(),
               "b": rng.integers(0, 256, 30).tolist(),
               "c": rng.integers(0, 256, 12).tolist(),
               "d": [5, 6, 7]}
    for rid, prompt in prompts.items():
        core.submit(prompt, max_tokens=40 if rid == "a" else 24, rid=rid)
    got = {rid: [] for rid in prompts}
    for _ in range(300):
        if not core.has_work:
            break
        for ev in core.step():
            if ev["token"] is not None:
                got[ev["rid"]].append(ev["token"])
        if len(got["c"]) == 5:
            core.cancel("c")
        held = [p for seq in core._running for p in seq.pages]
        assert len(held) == len(set(held)) == core.alloc.used_pages
        for seq in core._running:
            starts = seq.pages[::run]
            assert all(p % run == 0 for p in starts)
            assert seq.pages == [p + i for p in starts for i in range(run)]
            # (the page of the token asked for next comes with its step)
            assert len(seq.pages) >= -(-(seq.device_len - 1) // 8)
    assert not core.has_work and core.alloc.used_pages == 0
    return got, core


def test_an_engine_in_runs_serves_what_one_page_at_a_time_does(monkeypatch):
    _interpreted(monkeypatch, index_pages=4, attend_pages=8)
    cfg = tiny_sparse_mla_moe(index_topk=16)
    params = SparseMLAMoE(cfg).init(jax.random.PRNGKey(0))
    got, core = _serve_in_runs(monkeypatch, cfg, params, None)
    # the class's answer at these shapes: 1 KB a page of index keys, cut
    # to the blocks of 4 and 8 and the table of 16
    assert core.alloc.run == 4 and core.alloc.unused_pages == 1
    assert core.cache_stats()["page_run"] == 4
    assert core.device_stats()["decode_attention"] == "dsa_paged_attend"
    c = core.counters
    assert c["evictions"] > 0 and c["dsa_lanes_past_topk"] > 0
    assert len(got["a"]) == 40 and len(got["b"]) == len(got["d"]) == 24
    assert len(got["c"]) == 5
    # a copy a run: whole runs read, up to 3 pages a lane beyond the live
    assert c["kv_positions_read"] == c["kv_walk_copies"] * 4 * 8
    assert c["kv_positions_live"] <= c["kv_positions_read"] \
        < c["kv_positions_live"] + c["decode_lane_steps"] * 4 * 8
    want, plain = _serve_in_runs(monkeypatch, cfg, params, 1)
    assert plain.alloc.run == 1 and "page_run" not in plain.cache_stats()
    p = plain.counters
    assert p["kv_positions_read"] == p["kv_walk_copies"] * 8
    assert got == want


def test_a_config_names_the_class_and_refusals_are_plain():
    assert MODELS["sparse_mla_moe"] == (SparseMLAMoEConfig, SparseMLAMoE)
    cfg = model_config({"type": "sparse_mla_moe", "n_layers": 5,
                        "experts_held": (0, 16)})
    assert isinstance(build_model(cfg), SparseMLAMoE)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        32, 128, 2048)
    with pytest.raises(ValueError, match="experts_held"):
        SparseMLAMoEConfig(experts_held=(250, 16))
    with pytest.raises(ValueError, match="sigmoid"):
        SparseMLAMoEConfig(scoring_func="softmax")
    with pytest.raises(NotImplementedError, match="index keys"):
        SparseMLAMoE(tiny_sparse_mla_moe(), mesh=object())
    # a context that cannot pass index_topk is MLAMoE's program
    short = SparseMLAMoE(dataclasses.replace(tiny_sparse_mla_moe(),
                                             max_seq_len=32))
    assert short.decode_attention(16) in ("einsum",
                                          paged.KERNEL_MLA_PAGED_DECODE)
