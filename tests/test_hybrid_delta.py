"""The fourth architecture (`models.hybrid_delta.HybridDelta`: gated
delta-rule layers that hold a recurrent state of one size a sequence
beside full-attention layers that hold pages, behind one page table) held
to its plain reference (`benchmarks/models/hybrid_delta.py`) and to
itself: the two new kernels (through the Pallas interpreter) and the
triangular inverse against the recurrence written position by position,
the paged decode kernel at one query head a kv head, the allocator's fixed
class at one page a sequence, prefill then decode through the engine's own
programs, a slot reused, an inactive lane, eviction and re-prefill, and
what the engine counts and writes on its spans. Tiny sizes, CPU, seeded.
"""
import os
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
from ray_tpu.models import (HybridDelta, HybridDeltaConfig,  # noqa: E402
                            build_model, model_config)
from ray_tpu.models.hybrid_delta import tiny_hybrid_delta    # noqa: E402
from ray_tpu.ops import gated_delta as gd                    # noqa: E402
from ray_tpu.ops import conv                              # noqa: E402
from ray_tpu.ops import paged_attention as paged             # noqa: E402
from ray_tpu.ops.dispatch import compute_platform            # noqa: E402
from ray_tpu.serve.llm import spans as sp                    # noqa: E402
from ray_tpu.serve.llm.engine import EngineCore, _bucket     # noqa: E402
from ray_tpu.serve.llm.kv_cache import (PageAllocator,       # noqa: E402
                                        pages_from_budget, pages_needed)

CONFIG = "olmo-hybrid-7b-1chip"
PAGE = 8
H, DK, DV, C = 4, 8, 16, 8


# --------------------------------------------------- the recurrence's ops
def _case(s, seed=0):
    """q, k (H, s, dk) normed as a layer norms them, v, a log decay g and a
    beta that passes 1 (the eigenvalue 1 - beta negative)."""
    r = np.random.default_rng(seed)
    q = gd.l2_normalize(jnp.asarray(r.normal(size=(H, s, DK)))) / DK ** 0.5
    k = gd.l2_normalize(jnp.asarray(r.normal(size=(H, s, DK))))
    v = jnp.asarray(r.normal(size=(H, s, DV)), jnp.float32)
    g = -jnp.asarray(r.uniform(0.01, 1.5, size=(H, s)), jnp.float32)
    beta = jnp.asarray(r.uniform(0.2, 1.95, size=(H, s)), jnp.float32)
    assert float(beta.max()) > 1.5
    return q, k, v, g, beta


@pytest.mark.parametrize("size,block", [(64, 16), (8, 4), (8, 8), (32, 4)])
def test_the_triangular_inverse_is_the_inverse(size, block):
    A = np.tril(np.random.default_rng(size).normal(size=(size, size)) * 0.3,
                -1).astype(np.float32)
    got = gd.solve_unit_lower(jnp.asarray(A), block)
    np.testing.assert_allclose(got, np.linalg.inv(np.eye(size) + A),
                               atol=2e-5)


@pytest.mark.parametrize("true_len", [40, 37, 17, 5])
def test_chunk_kernel_matches_the_recurrence_and_stops_at_true_len(true_len):
    q, k, v, g, beta = _case(40)
    want_o, want_s = gd.gated_delta_recurrence(*(
        a[:, :true_len] for a in (q, k, v, g, beta)))
    for fn in (gd.gated_delta_prefill_kernel, gd.gated_delta_prefill):
        o, state = fn(q, k, v, g, beta, true_len, chunk=C)
        np.testing.assert_allclose(o[:, :true_len], want_o, atol=2e-6)
        # the state is the one at true_len, not at the bucket's end
        np.testing.assert_allclose(state, want_s, atol=2e-6)
    # past the last chunk that holds the prompt the kernel writes zeros
    o, _ = gd.gated_delta_prefill_kernel(q, k, v, g, beta, true_len, chunk=C)
    assert not np.asarray(o[:, -(-true_len // C) * C:]).any()


def test_the_plain_chunked_form_is_differentiable_and_carries_a_state():
    q, k, v, g, beta = _case(32, seed=1)
    _, mid = gd.gated_delta_chunked(*(a[:, :16] for a in (q, k, v, g, beta)),
                                    chunk=C)
    o2, end = gd.gated_delta_chunked(*(a[:, 16:] for a in (q, k, v, g, beta)),
                                     state=mid, chunk=C)
    want_o, want_s = gd.gated_delta_recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(o2, want_o[:, 16:], atol=2e-6)
    np.testing.assert_allclose(end, want_s, atol=2e-6)
    grad = jax.grad(lambda v_: gd.gated_delta_chunked(
        q, k, v_, g, beta, chunk=C)[0].sum())(v)
    assert np.isfinite(np.asarray(grad)).all() and np.asarray(grad).any()
    with pytest.raises(ValueError, match="whole chunks"):
        gd.gated_delta_chunked(*(a[:, :30] for a in (q, k, v, g, beta)),
                               chunk=C)


def test_step_kernel_matches_the_recurrence_and_writes_active_slots_only():
    q, k, v, g, beta = (a[:, :3].swapaxes(0, 1) for a in _case(3, seed=2))
    pool = np.random.default_rng(3).normal(
        size=(2, 5, DK, H * DV)).astype(np.float32)
    slots = jnp.asarray([2, -1, 0], jnp.int32)
    for fn in (gd.gated_delta_step_kernel, gd.gated_delta_step_reference):
        o, new = fn(q, k, v, g, beta, jnp.asarray(pool), 1, slots)
        new = np.asarray(new)
        for lane, slot in ((0, 2), (2, 0)):
            S0 = pool[1, slot].reshape(DK, H, DV).transpose(1, 0, 2)
            want_o, want_s = gd.gated_delta_recurrence(*(
                a[lane][:, None] for a in (q, k, v, g, beta)),
                state=jnp.asarray(S0))
            np.testing.assert_allclose(o[lane], want_o[:, 0], atol=2e-6)
            np.testing.assert_allclose(
                new[1, slot].reshape(DK, H, DV).transpose(1, 0, 2), want_s,
                atol=2e-6)
        # the other layer, the slots of no lane and nobody's: bit for bit
        assert (new[0] == pool[0]).all()
        assert (new[1, [1, 3, 4]] == pool[1, [1, 3, 4]]).all()


def test_the_convolution_continues_from_its_tail():
    r = np.random.default_rng(4)
    x = jnp.asarray(r.normal(size=(12, 6)), jnp.float32)
    w = jnp.asarray(r.normal(size=(4, 6)), jnp.float32)
    whole, _ = conv.causal_conv(x, w)
    _, tail = conv.causal_conv(x, w, 9)       # a bucket of 12, 9 real
    np.testing.assert_array_equal(tail, x[6:9])
    y, new_tail = conv.conv_step(x[9][None], tail[None], w)
    np.testing.assert_allclose(y[0], whole[9], atol=1e-6)
    np.testing.assert_array_equal(new_tail[0], x[7:10])
    # a prompt shorter than the tail: zeros before the sequence
    np.testing.assert_array_equal(
        conv.causal_conv(x, w, 2)[1],
        jnp.concatenate([jnp.zeros((1, 6)), x[:2]]))


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _flat_tail_step(x, w, flat, layer, slots, bias=None):
    """What the three classes did before PR 51, on the pool as it was
    (layers, slots + 1, (width - 1) x channels): gather by slot (slot 0's
    rows for a lane without one), `conv_step`, scatter with a write past
    the pool dropped."""
    B, width = x.shape[0], w.shape[0]
    tail = flat[layer, jnp.clip(slots, 0, flat.shape[1] - 1)].reshape(
        B, width - 1, -1)
    y, tail = conv.conv_step(x, tail, w, bias)
    where = jnp.where(slots >= 0, slots, flat.shape[1])
    return y, flat.at[layer, where].set(
        tail.reshape(B, -1).astype(flat.dtype), mode="drop")


# the three classes' convolutions (Olmo-Hybrid's 2 x 30 x 96 + 30 x 192
# channels, Ling's 3 x 32 x 128, Nemotron's 8192 + 2 x 1024 under a bias) at
# a few lanes, and channels that are no whole lanes (one row an input)
@pytest.mark.parametrize("pool_dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("channels,bias,slots", [
    (11520, False, (2, -1, 0)),         # a lane without a slot
    (12288, False, (-1, 4, -1, 1)),     # two: neither writes
    (10240, True, (3, 0, 1, 2)),
    (10240, True, (-1, -1)),            # a step of no active lane
    (96, False, (1, -1, 3)), (96, True, (0, -1, -1)),
])
def test_conv_tail_step_on_whole_tiles_is_the_flat_pools_step_bit_for_bit(
        channels, bias, slots, pool_dtype):
    r = np.random.default_rng(channels + len(slots))
    width, B = 4, len(slots)
    ad = jnp.dtype(pool_dtype)
    fold = conv.tail_shape(width, channels)
    rows = jnp.asarray(r.normal(size=(2, 6, width - 1, channels)), ad)
    rows = rows.at[1, 1].set(0)         # a fresh slot: zeros before it
    pool = conv.fold_tail(rows, fold)
    assert pool.shape == (2, 6) + fold
    x = jnp.asarray(r.normal(size=(B, channels)), ad)
    w = jnp.asarray(r.normal(size=(width, channels)), ad)
    b = jnp.asarray(r.normal(size=(channels,)), ad) if bias else None
    slots = jnp.asarray(slots, jnp.int32)

    want_y, want_flat = jax.jit(_flat_tail_step, static_argnums=3)(
        x, w, rows.reshape(2, 6, -1), 1, slots, b)
    y, new = jax.jit(conv.conv_tail_step, static_argnums=3)(
        x, w, pool, 1, slots, b)
    assert y.dtype == x.dtype and new.dtype == pool.dtype
    # (a lane without a slot convolves nobody's rows here and slot 0's
    # there: an output nobody reads)
    on = np.asarray(slots) >= 0
    assert (_bits(y)[on] == _bits(want_y)[on]).all()
    # the whole pool: the flat one's numbers, zeros past the channels
    assert (_bits(new) == _bits(conv.fold_tail(
        want_flat.reshape(rows.shape), fold))).all()
    # the other layer, the slots of no lane and nobody's: as they were;
    # an active lane's rows: its last two inputs and the new one
    new, pool = np.asarray(new, np.float32), np.asarray(pool, np.float32)
    held = {int(s) for s in slots if s >= 0}
    rest = [i for i in range(6) if i not in held]
    assert (new[0] == pool[0]).all() and (new[1, rest] == pool[1, rest]).all()
    for lane, slot in enumerate(np.asarray(slots)):
        if slot >= 0:
            assert (new[1, slot, :2] == pool[1, slot, 1:]).all()
            assert (new[1, slot, 2].reshape(-1)[:channels]
                    == np.asarray(x[lane], np.float32)).all()
            # and its output the convolution over the four, written out
            four = np.concatenate([pool[1, slot].reshape(3, -1)[:, :channels],
                                   np.asarray(x[lane], np.float32)[None]])
            z = (four * np.asarray(w, np.float32)).sum(0) + (
                np.asarray(b, np.float32) if bias else 0.0)
            np.testing.assert_allclose(
                np.asarray(y[lane], np.float32), z / (1 + np.exp(-z)),
                rtol=2e-2 if pool_dtype == "bfloat16" else 1e-5, atol=1e-5)


def test_conv_tail_step_takes_a_pool_of_another_dtype_than_the_inputs():
    """A float32 pool under bfloat16 activations (and the other way): the
    window is promoted as `jnp.concatenate` promotes it, the rows written
    back in the pool's dtype, as on the flat pool."""
    r = np.random.default_rng(5)
    for pool_dt, x_dt in ((jnp.float32, jnp.bfloat16),
                          (jnp.bfloat16, jnp.float32)):
        rows = jnp.asarray(r.normal(size=(1, 4, 3, 256)), pool_dt)
        fold = conv.tail_shape(4, 256)
        x = jnp.asarray(r.normal(size=(3, 256)), x_dt)
        w = jnp.asarray(r.normal(size=(4, 256)), x_dt)
        slots = jnp.asarray([2, -1, 0], jnp.int32)
        want_y, want_flat = jax.jit(_flat_tail_step, static_argnums=3)(
            x, w, rows.reshape(1, 4, -1), 0, slots)
        y, new = jax.jit(conv.conv_tail_step, static_argnums=3)(
            x, w, conv.fold_tail(rows, fold), 0, slots)
        want = conv.fold_tail(want_flat.reshape(rows.shape), fold)
        for a, b in ((y[::2], want_y[::2]), (new, want)):   # the active lanes
            assert a.dtype == b.dtype and (_bits(a) == _bits(b)).all()


def test_kernels_tile_the_published_shapes_and_say_where_they_run():
    assert gd.chunk_tiles(96, 192, 64, jnp.bfloat16)
    assert gd.chunk_heads(30) == 6 and gd.chunk_heads(4) == 4
    # 10 heads of 192 a grid step: whole lanes, 737 KB of state
    assert gd.step_columns(30, 96, 192) == 1920
    assert gd.step_tiles(30, 96, 192) and not gd.step_tiles(4, 8, 16)
    assert not gd.uses_step_kernel(30, 96, 192)         # this is a CPU
    # an input of the convolution: 90 rows of whole lanes in 96, whole
    # tiles (Ling's 96 and Nemotron's 80 are)
    assert conv.tail_shape(4, 11520) == (3, 96, 128)
    assert conv.tail_shape(4, 10240) == (3, 80, 128)
    assert conv.tail_shape(4, 96) == (3, 1, 96)
    with compute_platform("tpu"):
        assert gd.uses_step_kernel(30, 96, 192)
        assert gd.uses_chunk_kernel(96, 192, 64, jnp.bfloat16)


# --------------------- the page walk at one query head a kv head
@pytest.mark.parametrize("heads,lengths", [(30, (40, 1, 0, 17)),
                                           (5, (16, 33))])
def test_paged_decode_kernel_at_a_group_of_one_matches_masked_einsums(
        heads, lengths):
    page, hd = 16, 128
    B, mp = len(lengths), 3
    r = np.random.default_rng(heads)
    pools = [jnp.asarray(r.normal(size=(2, B * mp + 1, page, heads * hd)),
                         jnp.float32) for _ in range(2)]
    tables = np.full((B, mp), -1, np.int32)
    perm = r.permutation(B * mp)
    for b, n in enumerate(lengths):
        held = pages_needed(n, page)
        tables[b, :held] = perm[b * mp:b * mp + held]
    q = jnp.asarray(r.normal(size=(B, heads, hd)), jnp.float32)
    args = (q, *pools, 1, jnp.asarray(tables), jnp.asarray(lengths))
    got = paged.paged_decode_attention_kernel(*args)
    want = paged.paged_attention_reference(*args)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # against the masked einsum written out, one lane
    b, n = 0, lengths[0]
    keys = jnp.concatenate([pools[0][1, p] for p in tables[b, :mp]
                            if p >= 0])[:n].reshape(n, heads, hd)
    vals = jnp.concatenate([pools[1][1, p] for p in tables[b, :mp]
                            if p >= 0])[:n].reshape(n, heads, hd)
    probs = jax.nn.softmax(
        jnp.einsum("hd,nhd->hn", q[b], keys) / hd ** 0.5, axis=-1)
    np.testing.assert_allclose(
        got[b], jnp.einsum("hn,nhd->hd", probs, vals), atol=2e-5)


# ------------------------- the allocator's fixed class, one page a sequence
def test_fixed_class_at_one_page_a_sequence():
    a = PageAllocator(12, fixed=1, sequences=3)
    assert a.fixed_pages == 3 and a.free_pages == 12
    first = a.alloc(4)          # admission: its slot, then three others
    assert first == [0, 3, 4, 5]
    assert a.alloc(1) == [1]                    # a one-page sequence
    assert a.alloc(1, held=1) == [6]            # its extension: no slot
    assert a.alloc(2) == [2, 7] and a.fixed_used == 3
    assert a.alloc(1) is None                   # a fourth: no slot left
    assert a.alloc(3, held=4) == [8, 9, 10]     # a holder still extends
    a.free([1, 6])
    assert a.fixed_used == 2 and a.alloc(2) == [1, 6]
    with pytest.raises(ValueError, match="freed twice"):
        a.free([0, 0])
    # a lone sequence: one slot and every page of the other class
    assert a.fits(1 + 9) and not a.fits(1 + 10)


# ------------------------------------------------- the model, end to end
@pytest.fixture(scope="module")
def tiny_ref():
    """(model module, its Sizes at the tiny size but two periods deep,
    seeded float32 weights, the program's config for them): twice three
    linear layers and a full one, 4 heads of 8 / 16, chunks of 8."""
    cfg = modelcfg.load_config(CONFIG)
    mod = modelcfg.load_model(cfg)
    small = dict(mod.tiny(cfg), num_hidden_layers=8,
                 layer_types=cfg["layer_types"][:8])
    sz = mod.sizes(small)
    params = make_weights(mod.weight_shapes(sz), 11, dtype=jnp.float32)
    pc = mod.program_config(small, 256, dtype="float32",
                            param_dtype="float32")
    return mod, sz, params, pc


def test_apply_matches_the_reference_logits(tiny_ref):
    mod, sz, params, pc = tiny_ref
    toks = np.zeros((128,), np.int32)
    toks[:100] = np.random.default_rng(0).integers(0, sz.vocab, 100)
    got = build_model(pc).apply(params, jnp.asarray(toks[None, :100]))[0]
    want = mod.reference_rows(sz, params, jnp.asarray(toks), jnp.int32(0),
                              100)
    assert rel_rms(got, want) < 2e-4
    assert build_model(pc).param_count() == mod.param_count(sz)
    loss = build_model(pc).loss(params, {"tokens": jnp.asarray(
        toks[None, :64])})
    want_loss = mod.loss_fn(sz, params, jnp.asarray(toks[:64]))
    assert abs(float(loss) - float(want_loss)) < 1e-4


def _prefill(core, toks, p, pages):
    pt = np.full((core.max_pages_per_seq,), -1, np.int32)
    pt[:len(pages)] = pages
    s_pad = _bucket(p, hi=core.config.max_seq_len)
    padded = np.zeros((s_pad,), np.int32)
    padded[:p] = toks[:p]
    logits, core._cache = core._prefill_fn(s_pad)(
        core.params, jnp.asarray(padded), jnp.int32(p), jnp.asarray(pt),
        core._cache)
    return logits, pt


def _step(core, lanes):
    """One decode step of `lanes`: lane -> (token, position, table)."""
    B = core.max_batch
    tokens, positions = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
    pts = np.full((B, core.max_pages_per_seq), -1, np.int32)
    active = np.zeros((B,), bool)
    for lane, (tok, pos, pt) in lanes.items():
        tokens[lane], positions[lane], pts[lane] = tok, pos, pt
        active[lane] = True
    logits, core._cache = core._decode_fn(
        core.params, core._cache, jnp.asarray(tokens),
        jnp.asarray(positions), jnp.asarray(pts), jnp.asarray(active))
    return logits


def _through_the_engine(core, toks, p, steps, lane):
    """The harness's check (`serve_cell.check_against_reference`): one
    `alloc`, the engine's own prefill program, then its decode program."""
    pages = core.alloc.alloc(pages_needed(p + steps, core.page_size))
    logits, pt = _prefill(core, toks, p, pages)
    rows = [logits]
    for k in range(steps):
        rows.append(_step(core, {lane: (toks[p + k], p + k, pt)})[lane])
    core.alloc.free(pages)
    return jnp.stack(rows)


@pytest.mark.parametrize("p,steps", [
    (5, 12),        # shorter than a chunk of 8, in a bucket of 16
    (20, 9),        # not whole chunks; the tail's last 3 real inputs
    (33, 30),       # a bucket of 64, nearly twice the prompt
    (64, 3),        # whole chunks, a bucket that is full
])
def test_prefill_then_decode_through_the_engine_matches_the_reference(
        tiny_ref, p, steps):
    mod, sz, params, pc = tiny_ref
    core = EngineCore(pc, params, num_pages=0, page_size=PAGE, max_batch=3)
    assert core.alloc.fixed == 1 and core.alloc.fixed_pages == 3
    toks = np.zeros((256,), np.int32)
    toks[:p + steps] = np.random.default_rng(p).integers(0, sz.vocab,
                                                         p + steps)
    got = _through_the_engine(core, toks, p, steps, lane=1)
    want = mod.reference_rows(sz, params, jnp.asarray(toks),
                              jnp.int32(p - 1), steps + 1)
    assert rel_rms(got, want) < 2e-4
    assert core.alloc.free_pages == core.num_pages


def test_the_kernels_under_the_interpreter_give_the_same_logits(
        tiny_ref, monkeypatch):
    """The same check with the kernels of the served path forced on (the
    Pallas interpreter off the TPU): the chunked scan and the recurrence's
    step."""
    mod, sz, params, pc = tiny_ref
    monkeypatch.setattr(gd, "gated_delta_prefill",
                        gd.gated_delta_prefill_kernel)
    monkeypatch.setattr(gd, "gated_delta_step", gd.gated_delta_step_kernel)
    core = EngineCore(pc, params, num_pages=0, page_size=PAGE, max_batch=2)
    p, steps = 21, 8
    toks = np.zeros((256,), np.int32)
    toks[:p + steps] = np.random.default_rng(3).integers(0, sz.vocab,
                                                         p + steps)
    got = _through_the_engine(core, toks, p, steps, lane=1)
    want = mod.reference_rows(sz, params, jnp.asarray(toks),
                              jnp.int32(p - 1), steps + 1)
    assert rel_rms(got, want) < 2e-4


def test_prefill_of_n_then_m_steps_is_a_prefill_of_n_plus_m(tiny_ref):
    _, sz, params, pc = tiny_ref
    core = EngineCore(pc, params, num_pages=0, page_size=PAGE, max_batch=2)
    n, m = 19, 14
    toks = np.random.default_rng(7).integers(0, sz.vocab, n + m + 1)
    stepped = _through_the_engine(core, toks, n, m, lane=0)[-1]
    pages = core.alloc.alloc(pages_needed(n + m, PAGE))
    whole, pt = _prefill(core, toks, n + m, pages)
    np.testing.assert_allclose(stepped, whole, atol=1e-5)
    # and the states they leave agree: one more step from each
    after = _step(core, {1: (toks[n + m], n + m, pt)})[1]
    core.alloc.free(pages)
    again = _through_the_engine(core, toks, n, m + 1, lane=0)[-1]
    np.testing.assert_allclose(after, again, atol=1e-5)


def test_a_reused_slot_gives_what_a_fresh_engine_gives(tiny_ref):
    _, sz, params, pc = tiny_ref
    r = np.random.default_rng(8)
    first, second = (r.integers(0, sz.vocab, 60) for _ in range(2))
    used = EngineCore(pc, params, num_pages=0, page_size=PAGE, max_batch=2)
    _through_the_engine(used, first, 40, 20, lane=0)    # slot 0, then freed
    got = _through_the_engine(used, second, 11, 9, lane=1)   # slot 0 again
    fresh = EngineCore(pc, params, num_pages=0, page_size=PAGE, max_batch=2)
    want = _through_the_engine(fresh, second, 11, 9, lane=1)
    np.testing.assert_array_equal(got, want)


def test_an_inactive_lane_and_an_unassigned_table_write_nothing(tiny_ref):
    _, sz, params, pc = tiny_ref
    core = EngineCore(pc, params, num_pages=0, page_size=PAGE, max_batch=3)
    toks = np.random.default_rng(9).integers(0, sz.vocab, 40)
    pages = [core.alloc.alloc(3) for _ in range(2)]
    tables = [_prefill(core, toks[i:], 17, pages[i])[1] for i in range(2)]
    before = jax.tree.map(np.asarray, core._cache)
    # lane 0 runs sequence 0; sequence 1 holds its slot and no lane
    _step(core, {0: (toks[20], 17, tables[0])})
    after = jax.tree.map(np.asarray, core._cache)
    mine, other = pages[0][0], pages[1][0]
    for name in ("state", "tail"):
        assert (after[name][:, other] == before[name][:, other]).all()
        assert (after[name][:, -1] == before[name][:, -1]).all()  # nobody's
        assert (after[name][:, mine] != before[name][:, mine]).any()
    # a step of no active lane, and of a lane whose table is unassigned
    # (-1 everywhere), leaves every pool bit for bit as it was
    _step(core, {})
    _step(core, {2: (toks[3], 5, np.full_like(tables[0], -1))})
    for name, a in jax.tree.map(np.asarray, core._cache).items():
        assert (a == after[name]).all(), name


def test_a_state_costs_a_sequence_the_same_at_any_length(tiny_ref):
    _, sz, params, pc = tiny_ref
    core = EngineCore(pc, params, num_pages=0, page_size=PAGE, max_batch=2)
    cache, model = core._cache, core.model
    # 2 full layers over every page; 6 linear layers over 2 slots + nobody's
    assert cache["k"].shape == (2, core.num_pages, PAGE, 64)
    assert cache["state"].shape == (6, 3, DK, H * DV)
    assert cache["state"].dtype == jnp.float32
    # a slot's three inputs, each 128 channels folded into rows of lanes,
    # a whole tile of rows
    assert cache["tail"].shape == (6, 3, 3, 16, 2 * H * DK + H * DV)
    state = 6 * (DK * H * DV * 4 + 3 * 16 * 128 * 4)
    assert model.state_bytes() == state
    assert model.cache_page_bytes(PAGE, fixed=True) == state
    assert model.cache_page_bytes(PAGE) == 2 * 2 * PAGE * 64 * 4
    assert model.fixed_step_counts(100, PAGE) == model.fixed_step_counts(
        3000, PAGE) == {"state_slots": 1, "state_bytes": 2 * state}
    # the states are paid first, the rest buys pages of the full pools
    page = model.cache_page_bytes(PAGE)
    assert pages_from_budget(pc, PAGE, 2 * state + 7 * page + 5,
                             sequences=2) == 7
    st = core.device_stats()
    assert st["decode_attention"] == "einsum"
    assert st["fixed_pages"] == 2 and st["fixed_pages_used"] == 0
    with compute_platform("tpu"):
        served = HybridDelta(HybridDeltaConfig())
        assert served.decode_attention(16) == (
            "paged_decode_attn+gated_delta_step")
    assert served.fixed_pages(16) == 1
    # 3 linear layers: 96 x 5760 float32 of state, 3 x 11520 bf16 of tail
    # in 96 rows of 128 lanes, as the pool holds them
    assert served.state_bytes() == 3 * (96 * 5760 * 4 + 3 * 96 * 128 * 2)
    assert served.cache_page_bytes(16) == 2 * 16 * 3840 * 2


def test_the_engine_counts_state_and_writes_it_on_its_spans(
        tiny_ref, monkeypatch):
    _, sz, params, pc = tiny_ref
    seen = []

    class Recorder(sp.span):
        def __init__(self, name, **attributes):
            seen.append((name, attributes))
            super().__init__(name, **attributes)

    monkeypatch.setattr(sp, "span", Recorder)
    core = EngineCore(pc, params, num_pages=0, page_size=PAGE, max_batch=2)
    core.submit(list(range(1, 31)), max_tokens=6, rid="long")
    core.submit([7, 8, 9], max_tokens=6, rid="short")
    while core.has_work:
        core.step()
        if core._running:
            assert 0 < core.cache_stats()["fixed_pages_used"] <= 2
    c = core.counters
    per_lane = 2 * core.model.state_bytes()
    assert c["state_slots_live"] == c["decode_lane_steps"] > 0
    assert c["state_bytes_moved"] == per_lane * c["state_slots_live"]
    assert c["kv_window_positions_live"] == 0       # no window layer
    dispatches = [a for n, a in seen if n == sp.DISPATCH]
    assert dispatches and all(
        a["state_slots"] == a["lanes"]
        and a["state_bytes"] == per_lane * a["lanes"] for a in dispatches)
    prefills = {a["rid"]: a for n, a in seen if n == sp.PREFILL}
    assert prefills["long"]["scan_chunks"] == 4         # 30 tokens, C = 8
    assert prefills["short"]["scan_chunks"] == 1
    assert core.cache_stats()["fixed_pages_used"] == 0


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
    cfg = tiny_hybrid_delta()
    model = HybridDelta(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    # two slots and 6 more pages: the two sequences cannot both grow to 5
    # pages, the youngest is evicted, frees its slot, and is prefilled
    # again (into whichever slot is free) with what it had emitted
    core = EngineCore(cfg, params, num_pages=8, page_size=PAGE, max_batch=2)
    assert core.alloc.fixed_pages == 2
    prompts = {"a": list(range(3, 23)), "b": [5, 6, 7] * 7}
    core.submit(prompts["a"], max_tokens=18, rid="a")
    core.submit(prompts["b"], max_tokens=19, rid="b")
    got = {rid: [] for rid in prompts}
    for _ in range(400):
        if not core.has_work:
            break
        for ev in core.step():
            got[ev["rid"]].append(ev["token"])
    assert core.counters["evictions"] >= 1
    assert core.alloc.free_pages == 8 and core.alloc.fixed_used == 0
    for rid, n in (("a", 18), ("b", 19)):
        assert got[rid] == _greedy(model, params, prompts[rid], n), rid
    # cancelled mid-flight, a request gives its slot back
    core.submit(prompts["a"], max_tokens=18, rid="c")
    core.step()
    assert core.alloc.fixed_used == 1 and core.cancel("c")
    assert core.alloc.fixed_used == 0 and core.alloc.free_pages == 8


def test_a_config_names_its_model_and_refusals_are_plain():
    cfg = model_config({
        "type": "hybrid_delta", "d_model": 64, "n_heads": 4,
        "n_kv_heads": 2, "layer_types": ["linear_attention",
                                         "full_attention"],
        "linear_heads": 2, "linear_key_dim": 8, "linear_value_dim": 16,
        "chunk": 8, "d_ff": 128})
    assert isinstance(cfg, HybridDeltaConfig) and hash(cfg)
    assert isinstance(build_model(cfg), HybridDelta)
    assert cfg.linear_layers == (0,) and cfg.full_layers == (1,)
    assert cfg.head_dim == 16 and cfg.kv_dim == 32
    assert cfg.conv_channels == 2 * 16 + 32
    from ray_tpu.parallel.mesh import MeshSpec
    mesh = MeshSpec(dp=1, tp=2).build(jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="no mesh"):
        HybridDelta(tiny_hybrid_delta(), mesh=mesh)
    with pytest.raises(ValueError, match="not built"):
        HybridDeltaConfig(layer_types=("sliding_attention",))
    # a model without linear layers keeps nothing of a sequence for ever
    assert HybridDelta(HybridDeltaConfig(
        layer_types=("full_attention",))).fixed_pages(16) == 0
