"""The third architecture (`models.gqa_window_moe.GQAWindowMoE`: full and
sliding-window attention layers with unlike head counts behind one page
table, a per-head output gate, two rotary schemes, a dense and routed
feed-forwards) held to its plain reference (`benchmarks/models/
gqa_window_moe.py`) and to itself: the rotary formulas against numpy, the
windowed flash forward and the ring decode kernel (through the Pallas
interpreter) against masked einsums, the allocator's two classes, prefill
then decode through the engine's own programs across the ring's wrap,
eviction and re-prefill, and the programs of the older models pinned to
what they lowered to before the shared kernels changed (a window in
PR 35; a group of one query head and the fixed class's name in PR 37). Tiny
sizes, CPU, seeded.
"""
import hashlib
import math
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import modelcfg                      # noqa: E402
from benchmarks.harness.reference import rel_rms             # noqa: E402
from benchmarks.harness.weights import make_weights          # noqa: E402
from ray_tpu.models import (GatedConvMoEConfig, GQAWindowMoE,  # noqa: E402
                            GQAWindowMoEConfig, HybridDeltaConfig, HybridKDAMoEConfig,
                            HybridSSMMoEConfig, MLAMoE,
                            ParallelHybridConfig, ShortcutMLAMoEConfig,
                            SparseMLAMoEConfig,
                            Transformer, build_model, model_config)
from ray_tpu.models.config import TransformerConfig          # noqa: E402
from ray_tpu.models.gqa_window_moe import (RopeParams,       # noqa: E402
                                           tiny_gqa_window_moe)
from ray_tpu.models.mla_moe import tiny_mla_moe              # noqa: E402
from ray_tpu.ops import attention as attn                    # noqa: E402
from ray_tpu.ops import paged_attention as paged             # noqa: E402
from ray_tpu.ops import rope                                 # noqa: E402
from ray_tpu.ops.dispatch import compute_platform            # noqa: E402
from ray_tpu.serve.llm.engine import EngineCore, _bucket     # noqa: E402
from ray_tpu.serve.llm.kv_cache import (PageAllocator,       # noqa: E402
                                        pages_from_budget, pages_needed)

CONFIG = "laguna-xs.2-1chip"
PAGE = 8


@pytest.fixture(scope="module")
def tiny_ref():
    """(model module, its tiny Sizes, seeded float32 weights, the program's
    config for them): window 32, pages of 8, 2 full + 3 sliding layers of 4
    and 6 heads, 8 experts top-2."""
    cfg = modelcfg.load_config(CONFIG)
    mod = modelcfg.load_model(cfg)
    small = mod.tiny(cfg)
    sz = mod.sizes(small)
    params = make_weights(mod.weight_shapes(sz), 11, dtype=jnp.float32)
    pc = mod.program_config(small, 256, dtype="float32",
                            param_dtype="float32")
    return mod, sz, params, pc


# ------------------------------------------------------------ rotary
def _yarn_numpy(rot, theta, factor, orig, beta_fast, beta_slow):
    plain = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)

    def dim(beta):
        return rot * math.log(orig / (beta * 2 * math.pi)) / (
            2 * math.log(theta))
    low, high = max(math.floor(dim(beta_fast)), 0), min(
        math.ceil(dim(beta_slow)), rot - 1)
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0, 1)
    return plain / factor * ramp + plain * (1 - ramp)


def test_yarn_frequencies_match_the_formula_at_the_published_numbers():
    got = rope.yarn_frequencies(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    want = _yarn_numpy(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6)
    plain = np.asarray(rope.rope_frequencies(64, 500000.0))
    # the fastest pairs keep their frequency, the slowest turn 64x slower
    np.testing.assert_allclose(got[:4], plain[:4], rtol=1e-6)
    np.testing.assert_allclose(got[-4:], plain[-4:] / 64.0, rtol=1e-6)
    assert np.all(np.diff(np.asarray(got)) < 0)
    # the published attention factor is 0.1 ln(64) + 1
    assert 0.1 * math.log(64.0) + 1.0 == pytest.approx(1.4158883083359672)


@pytest.mark.parametrize("partial", [0.5, 1.0])
def test_partial_rotary_turns_the_leading_part_and_passes_the_rest(partial):
    hd, n, heads = 16, 9, 3
    rot = int(hd * partial)
    x = np.random.default_rng(0).normal(size=(n, heads, hd)).astype("f4")
    pos = np.arange(n) + 3
    p = RopeParams(rope_theta=100.0, rope_type="yarn",
                   partial_rotary_factor=partial, factor=8.0,
                   original_max_position_embeddings=32, beta_fast=8.0,
                   beta_slow=1.0)
    cos, sin = p.cos_sin(jnp.asarray(pos), hd)
    got = np.asarray(rope.rotate_leading(jnp.asarray(x), cos, sin))
    inv = _yarn_numpy(rot, 100.0, 8.0, 32, 8.0, 1.0)
    scale = 0.1 * math.log(8.0) + 1.0       # none given: the default
    ang = pos[:, None, None] * inv
    c, s = np.cos(ang) * scale, np.sin(ang) * scale
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    want = np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., rot:]],
                          -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[..., rot:], x[..., rot:])


def test_the_reference_writes_the_same_two_schemes_from_the_formulas(
        tiny_ref):
    mod, sz, _, pc = tiny_ref
    for r, p in ((sz.rope_full, pc.rope_full),
                 (sz.rope_sliding, pc.rope_sliding)):
        inv, scale = mod.rope_frequencies(r, sz.head_dim)
        cos, _ = p.cos_sin(jnp.arange(5), sz.head_dim)
        np.testing.assert_allclose(
            np.asarray(cos[:, 0]),
            np.cos(np.arange(5)[:, None] * np.asarray(inv)) * scale,
            rtol=1e-5, atol=1e-6)
    assert sz.rope_full.kind == "yarn" and sz.rope_sliding.kind == "default"


# ------------------------------------------- the windowed flash forward
def _masked_attention(q, k, v, window):
    """q (h, s, d), k / v (kvh, s, d): scores, the two masks written out."""
    h, s, d = q.shape
    rep = h // k.shape[0]
    k, v = np.repeat(k, rep, 0), np.repeat(v, rep, 0)
    scores = np.einsum("hqd,hkd->hqk", q, k) / math.sqrt(d)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (j <= i) & (i - j < window)
    scores = np.where(seen, scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    return np.einsum("hqk,hkd->hqd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("s,window,bq,bk,h,kvh", [
    (512, 128, 128, 128, 4, 2),     # blocks wholly below the window skipped
    (640, 100, 128, 128, 6, 1),     # a window that is no multiple of a block
    (384, 512, 128, 128, 2, 2),     # a window longer than the sequence
    (1024, 200, 256, 128, 2, 1),    # unlike blocks
    (320, 64, 128, 128, 8, 1),      # a tail block of keys
])
def test_windowed_flash_forward_matches_masked_einsum(s, window, bq, bk, h,
                                                      kvh):
    rng = np.random.default_rng(s + window)
    q = rng.normal(size=(h, s, 128)).astype("f4")
    k = rng.normal(size=(kvh, s, 128)).astype("f4")
    v = rng.normal(size=(kvh, s, 128)).astype("f4")
    got = attn.flash_window_attention_kernel(
        jnp.asarray(q[None]), jnp.asarray(k[None]), jnp.asarray(v[None]),
        window, block_q=bq, block_k=bk)[0]
    np.testing.assert_allclose(np.asarray(got),
                               _masked_attention(q, k, v, window),
                               rtol=2e-4, atol=2e-5)
    plain = attn.flash_attention(
        jnp.asarray(q[None]), jnp.asarray(k[None]), jnp.asarray(v[None]),
        window=window)[0]                       # off the TPU: the einsum
    np.testing.assert_allclose(np.asarray(plain), np.asarray(got),
                               rtol=2e-4, atol=2e-5)


def test_windowed_flash_grid_holds_only_the_blocks_a_window_reaches():
    """At 8192 tokens a late query block of a 512-window walks 6 key
    blocks of 128 (4 of 256), not 64 (32): read off the kernel's grid."""
    for block, reach in ((128, 6), (256, 4)):
        q = jax.ShapeDtypeStruct((1, 2, 8192, 128), jnp.bfloat16)
        with compute_platform("tpu"):
            text = str(jax.make_jaxpr(
                lambda a, b, c: attn.flash_attention(
                    a, b, c, window=512, block_q=block, block_k=block))(
                q, q, q))
        assert f"grid=(1, 2, {8192 // block}, {reach})" in text
        assert "flash_window_fwd" in text and "flash_fwd " not in text
    with pytest.raises(ValueError, match="forward only"):
        attn.flash_attention(jnp.zeros((1, 1, 8, 8)), jnp.zeros((1, 1, 8, 8)),
                             jnp.zeros((1, 1, 8, 8)), window=4,
                             return_lse=True)


# ----------------------------------------------- the ring decode kernel
def _ring_case(lengths, window, page, heads, kvh, hd=128, seed=0):
    """Sequences written into a ring of `ring_pages` pages a lane the way
    prefill and decode write them (logical page j at entry j mod ring, the
    newest winning), with the whole keys and values kept aside."""
    rng = np.random.default_rng(seed)
    ring = paged.ring_pages(window, page)
    B = len(lengths)
    num = B * ring + 2
    kp = rng.normal(size=(2, num, page, kvh * hd)).astype("f4")
    vp = rng.normal(size=(2, num, page, kvh * hd)).astype("f4")
    tables = np.full((B, ring), -1, np.int32)
    perm = rng.permutation(num)
    whole = []
    for b, n in enumerate(lengths):
        keys = rng.normal(size=(n, kvh * hd)).astype("f4")
        vals = rng.normal(size=(n, kvh * hd)).astype("f4")
        whole.append((keys, vals))
        for j in range(-(-n // page)):
            e = j % ring
            if tables[b, e] < 0:
                tables[b, e] = perm[b * ring + e]
            rows = slice(j * page, min((j + 1) * page, n))
            kp[1, tables[b, e], :rows.stop - rows.start] = keys[rows]
            vp[1, tables[b, e], :rows.stop - rows.start] = vals[rows]
    q = rng.normal(size=(B, heads, hd)).astype("f4")
    return q, kp, vp, tables, whole


def _window_einsum(q, whole, window, kvh):
    """Each lane's query against the last `window` of its whole keys."""
    out = np.zeros_like(q)
    g = q.shape[1] // kvh
    for b, (keys, vals) in enumerate(whole):
        n = len(keys)
        if not n:
            continue
        lo = max(0, n - window)
        k = keys[lo:].reshape(n - lo, kvh, -1)
        v = vals[lo:].reshape(n - lo, kvh, -1)
        for h in range(q.shape[1]):
            s = k[:, h // g] @ q[b, h] / math.sqrt(q.shape[-1])
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ v[:, h // g]
    return out


@pytest.mark.parametrize("lengths,heads,kvh", [
    ((5, 64, 200, 0), 4, 2),        # under the window, at it, wrapped, idle
    ((65, 79, 80, 81), 12, 2),      # around a page's edge; a group of 6
    ((1000, 33, 72, 513), 16, 2),   # wrapped many times; a group of 8
])
def test_ring_kernel_matches_masked_einsum_over_the_whole_sequence(
        lengths, heads, kvh):
    window, page = 64, 16
    q, kp, vp, tables, whole = _ring_case(lengths, window, page, heads, kvh)
    want = _window_einsum(q, whole, window, kvh)
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), 1,
            jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), window)
    ref = paged.paged_window_attention_reference(*args)
    np.testing.assert_allclose(np.asarray(ref), want, rtol=2e-4, atol=2e-5)
    got = paged.paged_window_decode_attention_kernel(*args)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("block", [33, 16])
@pytest.mark.parametrize("lengths,heads,kvh", [
    ((1000, 513, 528, 529), 2, 2),      # wrapped; the ring's edges; 1 a group
    ((4000, 512, 100, 0), 4, 2),        # wrapped many times, under, idle
    ((2000, 640, 641, 1), 12, 2),       # a group of 6; a position
])
def test_ring_of_33_pages_wraps_inside_a_block(walk_budget, block, lengths,
                                               heads, kvh):
    """The cells' ring (a window of 512 in 16-position pages) as one block
    of 33 pages, multiplied in one or two pieces of 8 or whole, and as
    blocks of 16: the ring's wrap falls inside a block, wherever the
    window begins."""
    window, page = 512, 16
    page_bytes = 2 * page * kvh * 128 * 4
    if block != 33:
        walk_budget(paged.BLOCK_SLOTS * block * page_bytes)
    assert paged.walk_block_pages(page_bytes, page, 33) == block
    assert paged.walk_prefixes(33, page) == (8, 16, 33)
    q, kp, vp, tables, whole = _ring_case(lengths, window, page, heads, kvh,
                                          seed=3)
    want = _window_einsum(q, whole, window, kvh)
    got = paged.paged_window_decode_attention_kernel(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), 1,
        jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), window)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_ring_walk_reads_at_most_the_ring_and_names_its_kernel():
    window, page = 512, 16
    ring = paged.ring_pages(window, page)
    assert ring == 33
    for length in (1, 100, 512, 513, 528, 529, 4000, 8192):
        live, read = paged.ring_walk(length, window, page)
        assert live == min(length, window)
        assert live <= read <= ring * page
    assert paged.ring_walk(8192, window, page) == (512, 512)
    assert paged.ring_walk(8185, window, page) == (512, 528)
    with compute_platform("tpu"):
        text = str(jax.make_jaxpr(
            lambda q, k, t, n: paged.paged_window_decode_attention(
                q, k, k, 0, t, n, window))(
            jax.ShapeDtypeStruct((4, 64, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((3, 132, 16, 1024), jnp.bfloat16),
            jax.ShapeDtypeStruct((4, ring), jnp.int32),
            jax.ShapeDtypeStruct((4,), jnp.int32)))
    assert "paged_window_decode_attn" in text
    with pytest.raises(ValueError, match="ring of 33"):
        paged.paged_window_decode_attention_kernel(
            jnp.zeros((1, 8, 128)), jnp.zeros((1, 40, 16, 128)),
            jnp.zeros((1, 40, 16, 128)), 0, jnp.zeros((1, 32), jnp.int32),
            jnp.ones((1,), jnp.int32), window)


# ------------------------------------------- the allocator's two classes
def test_one_class_is_the_allocator_it_always_was():
    a = PageAllocator(6)
    assert a.fixed_pages == 0 and a.alloc(3) == [0, 1, 2]
    assert a.alloc(1, held=3) == [3] and a.alloc(3) is None
    a.free([1])
    assert a.alloc(1) == [1] and a.free_pages == 2 and a.fits(6)
    assert not a.fits(7)


def test_two_classes_alloc_extend_free_and_double_free():
    a = PageAllocator(20, fixed=3, sequences=2)
    assert a.fixed_pages == 6 and a.free_pages == 20
    first = a.alloc(5)                  # admission: 3 of the ring, then 2
    assert first == [0, 1, 2, 6, 7]
    short = a.alloc(2)                  # a sequence under its ring
    assert short == [3, 4]
    assert a.alloc(1, held=2) == [5]    # its extension asks by what it holds
    assert a.alloc(1, held=3) == [8]    # past the ring: the other class
    assert a.fixed_used == 6 and a.used_pages == 9
    assert a.alloc(1) is None           # a third sequence: no ring left
    assert a.free_pages == 11           # and nothing was claimed
    a.free(short + [5])
    assert a.fixed_used == 3 and a.alloc(4) == [5, 4, 3, 9]
    with pytest.raises(ValueError, match="freed twice"):
        a.free([6, 6])
    # a lone sequence: its ring from the ring class, the rest from the other
    assert a.fits(3 + 14) and not a.fits(3 + 15)
    # a pool smaller than the rings asked for is all ring class
    small = PageAllocator(4, fixed=3, sequences=2)
    assert small.fixed_pages == 4 and small.fits(3) and not small.fits(4)


# ------------------------------------------------- the model, end to end
def test_apply_matches_the_reference_logits(tiny_ref):
    mod, sz, params, pc = tiny_ref
    toks = np.zeros((128,), np.int32)
    toks[:100] = np.random.default_rng(0).integers(0, sz.vocab, 100)
    got = build_model(pc).apply(params, jnp.asarray(toks[None, :100]))[0]
    want = mod.reference_rows(sz, params, jnp.asarray(toks), jnp.int32(0),
                              100)
    assert rel_rms(got, want) < 2e-4
    assert build_model(pc).param_count() == mod.param_count(sz)


def _through_the_engine(core, toks, p, steps, lane):
    """The harness's check (`serve_cell.check_against_reference`): one
    `alloc`, the engine's own prefill program, then its decode program."""
    pages = core.alloc.alloc(pages_needed(p + steps, core.page_size))
    pt = np.full((core.max_pages_per_seq,), -1, np.int32)
    pt[:len(pages)] = pages
    s_pad = _bucket(p, hi=core.config.max_seq_len)
    padded = np.zeros((s_pad,), np.int32)
    padded[:p] = toks[:p]
    logits, core._cache = core._prefill_fn(s_pad)(
        core.params, jnp.asarray(padded), jnp.int32(p), jnp.asarray(pt),
        core._cache)
    rows = [logits]
    B = core.max_batch
    for k in range(steps):
        tokens, positions = np.zeros((B,), np.int32), np.zeros((B,),
                                                               np.int32)
        pts = np.full((B, core.max_pages_per_seq), -1, np.int32)
        active = np.zeros((B,), bool)
        tokens[lane], positions[lane] = toks[p + k], p + k
        pts[lane], active[lane] = pt, True
        logits, core._cache = core._decode_fn(
            core.params, core._cache, jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(pts), jnp.asarray(active))
        rows.append(logits[lane])
    core.alloc.free(pages)
    return jnp.stack(rows)


@pytest.mark.parametrize("p,steps", [
    (5, 12),        # shorter than the window of 32
    (20, 30),       # crossing it: the ring fills and begins to wrap
    (100, 40),      # prefill writes the last 5 pages of 13; wrapped 3 times
    (40, 130),      # decode alone wraps the ring three times
])
def test_prefill_then_decode_through_the_engine_matches_the_reference(
        tiny_ref, p, steps):
    mod, sz, params, pc = tiny_ref
    core = EngineCore(pc, params, num_pages=0, page_size=PAGE, max_batch=3)
    assert core.alloc.fixed == 5 and core.alloc.fixed_pages == 15
    toks = np.zeros((256,), np.int32)
    toks[:p + steps] = np.random.default_rng(p).integers(0, sz.vocab,
                                                         p + steps)
    got = _through_the_engine(core, toks, p, steps, lane=1)
    want = mod.reference_rows(sz, params, jnp.asarray(toks),
                              jnp.int32(p - 1), steps + 1)
    assert rel_rms(got, want) < 2e-4
    assert core.alloc.free_pages == core.num_pages


def test_a_sliding_layers_cache_is_a_ring_and_the_engine_counts_it(tiny_ref):
    _, sz, params, pc = tiny_ref
    core = EngineCore(pc, params, num_pages=0, page_size=PAGE, max_batch=2)
    cache = core._cache
    # 2 full layers over every page, 3 sliding layers over the rings only
    assert cache["k"].shape == (2, core.num_pages, PAGE, sz.kv_dim)
    assert cache["wk"].shape == (3, 2 * 5, PAGE, sz.kv_dim)
    assert core.model.cache_page_bytes(PAGE) == 2 * 2 * PAGE * sz.kv_dim * 4
    assert core.model.cache_page_bytes(PAGE, fixed=True) == (
        2 * 3 * PAGE * sz.kv_dim * 4)
    # the ring is paid first, the rest buys pages of the full layers' pool
    page, ring = core.model.cache_page_bytes(PAGE), \
        core.model.cache_page_bytes(PAGE, fixed=True)
    assert pages_from_budget(pc, PAGE, 10 * ring + 7 * page,
                             sequences=2) == 7
    core.submit(list(range(1, 101)), max_tokens=20, rid="long")
    core.submit([7, 8, 9], max_tokens=20, rid="short")
    while core.has_work:
        core.step()
        if core._running:
            st = core.cache_stats()
            assert 0 < st["fixed_pages_used"] <= st["fixed_pages"] == 10
    c = core.counters
    # a lane 100-120 long holds 32 positions of a sliding layer and reads
    # at most the ring's 40; the short lane holds what it has
    assert c["kv_window_positions_live"] < c["kv_positions_live"]
    assert c["kv_window_positions_read"] <= c["decode_lane_steps"] * 40
    assert c["kv_window_positions_live"] <= c["kv_window_positions_read"]
    # the gather multiplies all it reads, in no blocks
    assert c["kv_window_positions_attended"] == c["kv_window_positions_read"]
    assert c["kv_positions_attended"] == c["kv_positions_read"]
    assert c["kv_window_walk_blocks"] == c["kv_walk_blocks"] == 0
    st = core.device_stats()
    assert st["decode_attention"] == "einsum"
    assert st["fixed_pages_used"] == 0 and np.asarray(
        st["moe_load"]).shape == (4, 8)
    assert c["moe_pairs"] == c["decode_lane_steps"] * 2 * 4
    with compute_platform("tpu"):
        served = GQAWindowMoE(GQAWindowMoEConfig())
        assert served.decode_attention(16) == (
            "paged_decode_attn+paged_window_decode_attn")
    assert served.window_pages(16) == served.fixed_pages(16) == 33
    # under the kernels a lane's ring is one block, multiplied as far as
    # the least of 8, 16 and 33 pages that holds what the walk reaches
    # (`ring_walk`'s read), a full layer's table blocks of the rule's size
    assert served.walk_block_pages(16, 33, fixed=True) == 33
    counts = served.fixed_step_counts(3400, 16)
    assert counts["window_walk_blocks"] == 1
    assert counts["window_positions_read"] == 33 * 16
    assert counts["window_positions_attended"] == 33 * 16
    assert served.fixed_step_counts(100, 16)[
        "window_positions_attended"] == 8 * 16
    assert served.fixed_step_counts(3408, 16)[
        "window_positions_read"] == 32 * 16
    assert served.fixed_step_counts(200, 16)[
        "window_positions_attended"] == 16 * 16
    block = served.walk_block_pages(16, 512)
    assert block == paged.walk_block_pages(2 * 16 * 1024 * 2, 16, 512)
    assert served.cache_page_bytes(16) == 2 * 2 * 16 * 1024 * 2
    assert served.param_count() == 3869857792


def _greedy(model, params, prompt, n):
    """Greedy tokens by the plain forward, one padded shape."""
    seq = list(prompt)
    apply = jax.jit(model.apply)
    for _ in range(n):
        padded = np.zeros((1, 64), np.int32)
        padded[0, :len(seq)] = seq
        seq.append(int(jnp.argmax(apply(params, jnp.asarray(padded))[
            0, len(seq) - 1])))
    return seq[len(prompt):]


def test_eviction_and_re_prefill_give_the_same_greedy_tokens():
    cfg = tiny_gqa_window_moe()
    model = GQAWindowMoE(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    # two rings of 5 pages and 3 more: the two that pass their rings (by 2
    # and 3 pages) cannot both stay, the youngest is evicted, frees both
    # classes and resumes
    core = EngineCore(cfg, params, num_pages=13, page_size=PAGE, max_batch=2)
    assert core.alloc.fixed_pages == 10
    prompts = {"a": list(range(3, 33)), "b": [5, 6, 7] * 9}
    core.submit(prompts["a"], max_tokens=26, rid="a")
    core.submit(prompts["b"], max_tokens=30, rid="b")
    got = {rid: [] for rid in prompts}
    for _ in range(400):
        if not core.has_work:
            break
        for ev in core.step():
            got[ev["rid"]].append(ev["token"])
    assert core.counters["evictions"] >= 1
    assert core.alloc.free_pages == 13 and core.alloc.fixed_used == 0
    for rid, n in (("a", 26), ("b", 30)):
        assert got[rid] == _greedy(model, params, prompts[rid], n), rid
    with pytest.raises(ValueError, match="pages"):
        core.submit(list(range(60)), max_tokens=30)     # 12 pages: 5 + 7


def test_a_config_names_its_model_and_refusals_are_plain():
    cfg = model_config({
        "type": "gqa_window_moe", "d_model": 64, "n_kv_heads": 2,
        "head_dim": 16, "layer_types": ["full_attention",
                                        "sliding_attention"],
        "n_heads_per_layer": [4, 6], "mlp_layer_types": ["dense", "sparse"],
        "rope_full": {"rope_theta": 100.0}, "num_experts": 4,
        "num_experts_per_tok": 2})
    assert isinstance(cfg, GQAWindowMoEConfig) and hash(cfg)
    assert isinstance(build_model(cfg), GQAWindowMoE)
    assert cfg.full_layers == (0,) and cfg.sparse_layers == (1,)
    assert isinstance(cfg.rope_full, RopeParams)
    from ray_tpu.parallel.mesh import MeshSpec
    mesh = MeshSpec(dp=1, tp=2).build(jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="no mesh"):
        GQAWindowMoE(tiny_gqa_window_moe(), mesh=mesh)
    with pytest.raises(ValueError, match="one entry a layer"):
        GQAWindowMoEConfig(layer_types=("full_attention",))
    with pytest.raises(ValueError, match="yarn"):
        RopeParams(rope_type="llama3")
    for model in (Transformer(TransformerConfig()),
                  MLAMoE(tiny_mla_moe())):
        assert model.fixed_pages(16) == 0


# ------------------- the older models' programs are what they were
#
# `flash_attention` took a window and `_walk_pages` a lower bound in this
# PR, and three accepted cells time those kernels. The traced programs of
# `Transformer` and `MLAMoE`, kernels and all (traced for a TPU, so the
# Pallas calls and their bodies are in the text), are pinned to the text
# the parent commit gave; a change to them is a change to those cells'
# programs and has to be meant.
def _program_text(fn, *args):
    with compute_platform("tpu"):
        text = str(jax.make_jaxpr(fn)(*args))
    return re.sub(r" at [^\s\]]+:\d+", "", text)     # source lines


def _programs(model, cfg, B=2, s=32, page=16):
    with compute_platform("tpu"):   # the table as wide as the class says
        mp = model.table_pages(page, cfg.max_seq_len // page)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    fixed = B * model.fixed_pages(page)
    cache = jax.eval_shape(lambda: model.init_cache(
        B * mp, page, **({"fixed_pages": fixed} if fixed else {})))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)     # noqa
    return {
        "prefill": _program_text(
            lambda p, t, n, pt, c: model.prefill(p, t, n, pt, c, page),
            params, i32(s), i32(), i32(mp), cache),
        "decode_step": _program_text(
            lambda p, c, t, pos, pts, a: model.decode_step(
                p, c, t, pos, pts, a, page),
            params, cache, i32(B), i32(B), i32(B, mp),
            jax.ShapeDtypeStruct((B,), jnp.bool_))}


PINNED = {
    ("Transformer", "prefill"): "40910654b9cf7e7c",
    # PR 38 changed the page walk (its block from the bytes of a page, a
    # block multiplied over the part a lane holds): the two decode steps
    # that hold a paged kernel are pinned anew to PR 38's text. The tiny
    # `MLAMoE`'s rows are no shape the latent kernel tiles, so its step
    # gathers and stands at PR 36's text, as the three prefills do.
    # PR 48 changed the walk again (a lane starts the first block of the
    # lane behind it, the slot carried from lane to lane in SMEM): the same
    # two steps are pinned anew to PR 48's text; the three prefills and
    # `MLAMoE`'s step keep their hashes, so nothing else moved.
    ("Transformer", "decode_step"): "e7da2271789da96f",
    ("MLAMoE", "prefill"): "50899f6b3446b401",
    ("MLAMoE", "decode_step"): "f5faaa6b1a81bca9",
    # PR 37 renamed the allocator's ring class and gave the page walk a
    # group of one query head: the third class is pinned to PR 36's text
    ("GQAWindowMoE", "prefill"): "1c076adecba546a0",
    ("GQAWindowMoE", "decode_step"): "d7ceb6a28ee7cb0b",
    # the three classes after it, pinned in PR 49 to PR 48's text before
    # what the classes share was lifted out of them (`models/paged.py`,
    # `models/gqa.py`), so that the lift is held by hashes it did not write.
    # PR 51 gave the pool of the convolution's tails whole tiles a slot
    # (`ops.conv.tail_shape`, `conv_tail_step`): the decode steps of
    # the three classes that keep a tail (gather, `conv_step` and scatter
    # over the new shape) and their prefills (`_write_slot` folds the tail
    # into it) are pinned anew to PR 51's text; `ShortcutMLAMoE`'s two and
    # the six before them keep their hashes
    ("HybridDelta", "prefill"): "328443fc4f95f8b1",
    ("HybridDelta", "decode_step"): "61459a317f791d1e",
    ("ShortcutMLAMoE", "prefill"): "d4d9ab62a550993a",
    # PR 64 let `_walk_pages` bring a run of pages a copy and gave the
    # latent kernel the run its class answers the engine with
    # (`LatentAttention.page_run`): at this config's rows of 256 in pages
    # of 16 (8 KB a page, tables of 8) that is 8, so the step's two latent
    # kernels are pinned anew to PR 64's text. With the run at 1 the text
    # is the parent's, b117688674ce4a5d (`PINNED_AT_RUN_1` below), and the
    # nineteen others keep their hashes: `MLAMoE`'s and `HybridKDAMoE`'s
    # tiny rows are no shape the latent kernel tiles, and the latter keeps
    # a fixed page, so a run of 1, whatever its rows
    ("ShortcutMLAMoE", "decode_step"): "4de7c6fe6411287f",
    ("HybridSSMMoE", "prefill"): "a63f8231742dfc7c",
    ("HybridSSMMoE", "decode_step"): "28b7025de7c15535",
    # the seventh class, pinned in PR 50 to the text PR 50 gave it: what it
    # shares (`models/latent.py` without a LoRA and with the heads' gate,
    # `route_topk` under a group limit, `ops/kda.py`) is held from here on;
    # anew in PR 51 with the two others that keep a tail
    ("HybridKDAMoE", "prefill"): "5dc53e652cdd1d41",
    ("HybridKDAMoE", "decode_step"): "8e185abd9caf302c",
    # PR 53 gave the latent prefills' flash forward blocks of 1024 x 1024
    # (`models.latent.PREFILL_BLOCKS`): over these configs' 128 tokens the
    # call cuts them to the 128 x 128 it had, so the three latent classes'
    # prefills keep their text and all fourteen their hashes.
    # PR 54 lifted the state-space mixer out of `HybridSSMMoE` into
    # `models/ssm.py` (a second class runs it) and let `ops.ssd.step_columns`
    # cut a group that is too wide for a block: `HybridSSMMoE`'s two keep
    # their hashes, as do the twelve others. The eighth class, pinned to the
    # text PR 54 gave it: five query heads a kv head, a scaled key rotated,
    # both mixers' kernels in the one layer
    ("ParallelHybrid", "prefill"): "21868d3701abe89c",
    ("ParallelHybrid", "decode_step"): "2438e0ed0cacbae7",
    # PR 58 let the page walk take heads that are a share of a 128-lane
    # (`ops.paged_attention.LANE`: the wrapper packs them, `_paged_decode_call`
    # is given its `sm_scale`), the convolution run without its SiLU
    # (`activate`), `StateSlots._write_slot` write a tail alone, the head be
    # the embedding's table (`PagedDecoder._head`) and `DenseOrRoutedFFN`
    # leave the shared expert out where a layer has none: all sixteen keep
    # their hashes. The ninth class, pinned to the text PR 58 gave it: two
    # kv heads of 64 as one lane under eight query rows, the gated
    # convolution, a router under a bias and no shared expert, a tied head
    ("GatedConvMoE", "prefill"): "fd25f7776bc01dfe",
    ("GatedConvMoE", "decode_step"): "d7858856f2850810",
    # the tenth class, pinned in PR 63 to PR 62's text (prefill
    # d9caf436e4f2bd1d, decode_step 5ee65bfddb48e168) before the mixers were
    # lifted out of the classes and `models/paged.py` walked a table of them.
    # PR 63's one walk holds eleven of the twenty texts byte for byte and
    # writes nine in another order, each shown by `tools/lowered_text.py
    # --tpu` (`graph_hashes`) to be the parent's graph of equations at these
    # shapes and at its cell's: `valid` after the prompt's page ids and not
    # before (the prefills of `MLAMoE`, `SparseMLAMoE`, `GQAWindowMoE`); a
    # step's three expert counts through `ExpertCounts._count_step`, the
    # maximum first (`MLAMoE`, `GQAWindowMoE`); a lane's entry, row and
    # length through `paged.lane_entries` (`HybridDelta`, `GQAWindowMoE`),
    # whose k and v are each flattened as it is written; an expert layer's
    # residual addition before its counts and not after (`HybridSSMMoE`'s
    # step); the attention's `[0]` before its pages are written and not
    # after the scan (`ParallelHybrid`'s prefill), both of whose programs
    # also lose the six equations a layer that built the column scales a
    # second time for nothing to read (`graph` the parent's, `equations`
    # six a layer fewer)
    ("SparseMLAMoE", "prefill"): "ea6ece939d3c610e",
    ("SparseMLAMoE", "decode_step"): "5ee65bfddb48e168",
}

# a class's configuration for its pin: small, and of head sizes that tile
# (heads and latents of 128, chunks of whole tiles, bfloat16), so that the
# paged, flash, delta and scan kernels are in the text
PINNED_CONFIGS = {
    "Transformer": lambda: TransformerConfig(
        vocab_size=256, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=512, max_seq_len=128),
    "MLAMoE": tiny_mla_moe,
    "GQAWindowMoE": lambda: GQAWindowMoEConfig(
        vocab_size=256, d_model=128, n_kv_heads=1, head_dim=128,
        layer_types=("full_attention", "sliding_attention"),
        n_heads_per_layer=(2, 4), mlp_layer_types=("dense", "sparse"),
        sliding_window=32, d_ff=256, moe_intermediate_size=128,
        shared_expert_intermediate_size=128, num_experts=8,
        num_experts_per_tok=2, max_seq_len=128),
    "HybridDelta": lambda: HybridDeltaConfig(
        vocab_size=256, d_model=256, n_heads=2, n_kv_heads=2,
        layer_types=("linear_attention", "full_attention"), linear_heads=2,
        linear_key_dim=64, linear_value_dim=128, chunk=16, d_ff=256,
        max_seq_len=128),
    "ShortcutMLAMoE": lambda: ShortcutMLAMoEConfig(
        vocab_size=256, d_model=128, n_layers=1, n_heads=2, q_lora_rank=64,
        kv_lora_rank=128, qk_nope_head_dim=64, qk_rope_head_dim=64,
        v_head_dim=64, d_ff=256, moe_intermediate_size=128,
        n_routed_experts=8, zero_expert_num=4, experts_held=(2, 4),
        num_experts_per_tok=2, max_seq_len=128),
    "HybridSSMMoE": lambda: HybridSSMMoEConfig(
        vocab_size=256, d_model=128, layer_types=tuple("ME*"), n_heads=2,
        n_kv_heads=1, head_dim=128, ssm_heads=2, ssm_head_dim=64,
        ssm_groups=1, ssm_state=128, chunk=128, moe_latent_size=64,
        moe_intermediate_size=128, shared_intermediate_size=128,
        n_routed_experts=8, experts_held=(2, 4), num_experts_per_tok=2,
        max_seq_len=128),
    "HybridKDAMoE": lambda: HybridKDAMoEConfig(
        vocab_size=256, d_model=128,
        layer_types=("linear_attention", "latent_attention"),
        mlp_layer_types=("dense", "sparse"), n_heads=2, linear_key_dim=128,
        linear_value_dim=128, chunk=16, kv_lora_rank=64,
        qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=64, d_ff=256,
        moe_intermediate_size=128, shared_intermediate_size=128,
        n_routed_experts=8, experts_held=(2, 4), num_experts_per_tok=2,
        n_group=2, topk_group=1, max_seq_len=128),
    "ParallelHybrid": lambda: ParallelHybridConfig(
        vocab_size=256, d_model=128, n_layers=1, n_heads=5, n_kv_heads=1,
        head_dim=128, ssm_heads=2, ssm_head_dim=128, ssm_groups=1,
        ssm_state=128, chunk=128, d_ff=256, max_seq_len=128),
    # (heads of 64, two a 128-lane of a pool row: the paged kernel tiles)
    "GatedConvMoE": lambda: GatedConvMoEConfig(
        vocab_size=256, d_model=128, layer_types=("conv", "full_attention"),
        n_heads=8, n_kv_heads=2, head_dim=64, d_ff=256,
        moe_intermediate_size=128, num_experts=8, num_experts_per_tok=2,
        num_dense_layers=1, max_seq_len=128),
    # (index keys and a latent of 128, tables of 8 pages of 16 that pass an
    # `index_topk` of 64: the step's two walk kernels tile and are in the
    # text, the prompt of 32 is `MLAMoE`'s prefill with the index keys
    # written beside it)
    "SparseMLAMoE": lambda: SparseMLAMoEConfig(
        vocab_size=256, d_model=128, n_layers=2, n_heads=2, q_lora_rank=64,
        kv_lora_rank=128, qk_nope_head_dim=64, qk_rope_head_dim=64,
        v_head_dim=64, d_ff=256, moe_intermediate_size=128,
        n_routed_experts=8, experts_held=(2, 4), num_experts_per_tok=2,
        first_k_dense_replace=1, index_n_heads=2, index_head_dim=128,
        index_topk=64, max_seq_len=128),
}


# PR 66 gave the per-head walk a run by what a pool's page weighs and let
# a class that keeps a fixed page lay runs behind it. These tiny configs'
# pages are 4-8 KB a pool, so every per-head class here would answer 8,
# where Laguna's and Olmo's deployments (32 KB a pool and more) answer 1
# and run the parent's programs: their two classes are traced at that
# answer and keep their hashes, as do `Transformer`'s (its own walk),
# the three latent classes' without a fixed page and `HybridKDAMoE`'s (its
# tiny rows gather). The three per-head classes that keep a slot and whose
# deployments ask for runs (`HybridSSMMoE`, `ParallelHybrid`,
# `GatedConvMoE`) are pinned anew to PR 66's text: tables of 1 + 8
# entries, the first a page a copy and a run of 8 behind it
AT_THEIR_DEPLOYMENTS_RUN = {"GQAWindowMoE": 1, "HybridDelta": 1}


@pytest.mark.parametrize("name,program", sorted(PINNED))
def test_older_models_programs_lower_to_the_parents_text(monkeypatch, name,
                                                         program):
    cfg = PINNED_CONFIGS[name]()
    model = build_model(cfg)
    if name in AT_THEIR_DEPLOYMENTS_RUN:
        run = AT_THEIR_DEPLOYMENTS_RUN[name]
        monkeypatch.setattr(type(model), "page_run", lambda self, *a: run)
    text = _programs(model, cfg)[program]
    assert "pallas_call" in text            # the kernels are in the text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PINNED[
        (name, program)]


# a pin PR 64 rewrote, as the parent wrote it: the text with the run at 1;
# and the six PR 66 rewrote (a prefill's text moves with its table's width
# alone), as PR 65 held them
PINNED_AT_RUN_1 = {("ShortcutMLAMoE", "decode_step"): "b117688674ce4a5d",
                   ("HybridSSMMoE", "prefill"): "2423f3d6a6654486",
                   ("HybridSSMMoE", "decode_step"): "f046ae2debee7f59",
                   ("ParallelHybrid", "prefill"): "0974a39ba83215f5",
                   ("ParallelHybrid", "decode_step"): "d62d5e9fcb638d6d",
                   ("GatedConvMoE", "prefill"): "f6c72aa2a2917f9b",
                   ("GatedConvMoE", "decode_step"): "5aa9c8af7f17b51c"}


@pytest.mark.parametrize("name,program", sorted(PINNED_AT_RUN_1))
def test_a_page_a_copy_is_the_parents_text(monkeypatch, name, program):
    """`run` 1 traces what the walk traced before it took a run."""
    cfg = PINNED_CONFIGS[name]()
    model = build_model(cfg)
    monkeypatch.setattr(type(model), "page_run", lambda self, *a: 1)
    text = _programs(model, cfg)[program]
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        PINNED_AT_RUN_1[(name, program)])


# What lets a pin be written anew: `tools/lowered_text.py`'s two hashes that
# no order of the equations moves, so that "the same equations in another
# order" is read off two numbers and not argued.
@pytest.mark.parametrize("change,graph,equations", [
    ("another order", True, True), ("another constant", False, False),
    ("a result nothing reads", True, False)])
def test_graph_hashes_see_through_an_order_and_nothing_else(change, graph,
                                                            equations):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    from lowered_text import graph_hashes

    def parent(x, y):
        a = jnp.sin(x) @ y
        b = jnp.where(y > 0, y, 2.0).sum(axis=0)
        return a + b

    def changed(x, y):
        b = jnp.where(y > 0, y, 3.0 if change == "another constant"
                      else 2.0).sum(axis=0)
        if change == "a result nothing reads":
            jnp.cos(x)
        a = jnp.sin(x) @ y
        return a + b

    x, y = jnp.ones((4, 8)), jnp.ones((8, 8))
    before, after = (jax.make_jaxpr(fn)(x, y) for fn in (parent, changed))
    assert str(before) != str(after)
    old, new = graph_hashes(before), graph_hashes(after)
    assert (old["graph"] == new["graph"]) is graph
    assert (old["equations"] == new["equations"]) is equations
    assert new["n_equations"] - old["n_equations"] == (
        change == "a result nothing reads")


# The dense prefill's flash blocks (`models/decode.py`) are the other GQA
# classes' (`gqa.FULL_BLOCKS`) cut to the bucket, whatever the config's
# `attn_block_q` / `attn_block_k`, which are the training step's and
# default to 128: a serving cell whose file states none must not walk
# 1,024 grid steps a layer at the 1024 bucket (PERF.md section 6, PR 57).
def _dense(**blocks):
    cfg = TransformerConfig(vocab_size=256, d_model=512, n_layers=2,
                            n_heads=4, n_kv_heads=2, d_ff=512,
                            max_seq_len=2048, **blocks)
    return build_model(cfg), cfg


def _flash_grids(text: str):
    """The grids over (batch, heads, query blocks, key blocks) in a text
    whose only such kernel is the flash forward."""
    assert "name=flash_fwd" in text and "flash_window_fwd" not in text
    return re.findall(r"grid=(\(\d+, \d+, \d+, \d+\))", text)


@pytest.mark.parametrize("bucket,blocks", [(512, 1), (1024, 1), (2048, 2)])
def test_dense_prefill_takes_its_flash_blocks_from_the_bucket(bucket, blocks):
    texts = [_programs(*_dense(**given), s=bucket)["prefill"] for given in
             ({}, {"attn_block_q": 256, "attn_block_k": 512})]
    assert _flash_grids(texts[0]) == [f"(1, 4, {blocks}, {blocks})"]
    assert texts[0] == texts[1]


def test_training_layer_still_follows_the_configs_blocks():
    for given, grid in (({}, "(1, 4, 8, 8)"),
                        ({"attn_block_q": 256, "attn_block_k": 512},
                         "(1, 4, 4, 2)")):
        model, _ = _dense(**given)
        text = _program_text(
            model.apply, jax.eval_shape(model.init, jax.random.PRNGKey(0)),
            jax.ShapeDtypeStruct((1, 1024), jnp.int32))
        assert _flash_grids(text) == [grid]
