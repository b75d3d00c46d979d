"""The ninth architecture (`models.gated_conv_moe.GatedConvMoE`: gated
short convolutions whose whole state is two rows a sequence, grouped-query
attention at heads of 64 in one layer of four, experts chosen under a bias
and no shared one, a tied head) held to its plain reference
(`benchmarks/models/lfm2_moe.py`) and to itself: the convolution with and
without its activation, prefill then decode through the engine's own
programs, the paged kernel under the interpreter at two kv heads a
128-lane, the fp8 control failing the same check, six faults that are this
model's own, a slot reused, an evicted sequence prefilled again, an
inactive lane, the tied head, and what the engine counts and writes on its
spans. Tiny sizes, CPU, seeded.
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
from benchmarks.harness.reference import rel_rms             # noqa: E402
from benchmarks.harness.weights import make_weights          # noqa: E402
from ray_tpu.models import (GatedConvMoE, GatedConvMoEConfig,  # noqa: E402
                            build_model, model_config)
from ray_tpu.models import gated_conv_moe as gcm             # noqa: E402
from ray_tpu.models import moe                               # noqa: E402
from ray_tpu.models.gated_conv_moe import (                  # noqa: E402
    tiny_gated_conv_moe)
from ray_tpu.ops import conv                              # noqa: E402
from ray_tpu.ops import paged_attention as paged             # noqa: E402
from ray_tpu.ops.dispatch import compute_platform            # noqa: E402
from ray_tpu.serve.llm import spans as sp                    # noqa: E402
from ray_tpu.serve.llm.engine import EngineCore              # noqa: E402
from test_hybrid_delta import (_greedy, _prefill, _step,     # noqa: E402
                               _through_the_engine)

CONFIG = "lfm2-8b-a1b-1chip"
PAGE = 8
TOL = 2e-4


# ------------------------------- the convolution, linear and activated
def _shifted_sums(x, w):
    """`y_t = sum_i w_i x_{t - width + 1 + i}`, zeros before the sequence,
    in float64 by hand."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    width = w.shape[0]
    padded = np.concatenate([np.zeros((width - 1, x.shape[1])), x])
    return sum(w[i] * padded[i:i + x.shape[0]] for i in range(width))


def _silu(y):
    return y / (1.0 + np.exp(-y))


@pytest.mark.parametrize("activate", [False, True])
def test_causal_conv_is_three_shifted_sums_then_silu_where_asked(activate):
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(12, 6)), jnp.float32)
    w = jnp.asarray(r.normal(size=(3, 6)), jnp.float32)
    want = _shifted_sums(x, w)
    got, tail = conv.causal_conv(x, w, activate=activate)
    np.testing.assert_allclose(got, _silu(want) if activate else want,
                               atol=1e-5)
    np.testing.assert_array_equal(tail, x[-2:])
    # the default is what the four recurrent classes call: activated
    np.testing.assert_array_equal(conv.causal_conv(x, w)[0],
                                  conv.causal_conv(x, w, activate=True)[0])
    assert (np.asarray(conv.causal_conv(x, w)[0])
            != np.asarray(conv.causal_conv(x, w, activate=False)[0])).any()


@pytest.mark.parametrize("true_len", [1, 2, 9])
def test_linear_conv_step_continues_a_prompt_shorter_than_the_taps(true_len):
    """A bucket of 12 with `true_len` real rows: the tail is the last two
    real inputs (zeros before the sequence where it is shorter than the
    taps), and a step from it is the whole sequence's next row."""
    r = np.random.default_rng(1)
    x = jnp.asarray(r.normal(size=(12, 4)), jnp.float32)
    w = jnp.asarray(r.normal(size=(3, 4)), jnp.float32)
    whole = _shifted_sums(x, w)
    _, tail = conv.causal_conv(x, w, true_len, activate=False)
    before = np.concatenate([np.zeros((2, 4)), np.asarray(x)])[
        true_len:true_len + 2]
    np.testing.assert_array_equal(tail, before)
    y, new = conv.conv_step(x[true_len][None], tail[None], w, activate=False)
    np.testing.assert_allclose(y[0], whole[true_len], atol=1e-5)
    np.testing.assert_array_equal(new[0], x[true_len - 1:true_len + 1]
                                  if true_len else new[0])
    ya, _ = conv.conv_step(x[true_len][None], tail[None], w)
    np.testing.assert_allclose(ya[0], _silu(whole[true_len]), atol=1e-5)


@pytest.mark.parametrize("activate", [False, True])
def test_conv_tail_step_against_the_pool_with_and_without_silu(activate):
    r = np.random.default_rng(2)
    channels, lanes = 256, 3
    shape = conv.tail_shape(3, channels)
    assert shape == (2, 16, 128)        # one bf16 tile a row
    pool = jnp.asarray(r.normal(size=(2, 5, *shape)), jnp.float32)
    pool = pool.at[..., channels // 128:, :].set(0.0)   # the rows' padding
    x = jnp.asarray(r.normal(size=(lanes, channels)), jnp.float32)
    w = jnp.asarray(r.normal(size=(3, channels)), jnp.float32)
    slots = jnp.asarray([2, -1, 0], jnp.int32)
    y, new = conv.conv_tail_step(x, w, pool, 1, slots, activate=activate)
    for lane, slot in ((0, 2), (2, 0)):
        rows = np.asarray(pool[1, slot]).reshape(2, -1)[:, :channels]
        want = _shifted_sums(np.concatenate([rows, x[lane][None]]), w)[-1]
        np.testing.assert_allclose(
            y[lane], _silu(want) if activate else want, atol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(new[1, slot]).reshape(2, -1)[:, :channels],
            np.concatenate([rows[1:], x[lane][None]]))
    # the inactive lane wrote nowhere: layer 0, the other slots, nobody's
    keep = np.ones(5, bool)
    keep[[0, 2]] = False
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[1, keep], pool[1, keep])


# ------------------------------------- the class against its reference
@pytest.fixture(scope="module")
def tiny_ref():
    """(model module, its Sizes at the tiny size, seeded float32 weights,
    the program's config for them): one period of both mixers, 8 query
    heads of 64 over 2 kv heads (a group of 4, two kv heads a 128-lane of a
    pool row), three taps, one dense layer and three of 8 experts top-2
    under a bias ten times the cell's (at these widths the scores spread
    by a fifth of what they do at the published ones)."""
    cfg = modelcfg.load_config(CONFIG)
    mod = modelcfg.load_model(cfg)
    small = dict(mod.tiny(cfg), num_attention_heads=8, head_dim=64)
    sz = mod.sizes(small)
    assert (sz.heads, sz.kv_heads, sz.head_dim, sz.kv_dim) == (8, 2, 64, 128)
    params = make_weights(mod.weight_shapes(sz), 11, dtype=jnp.float32)
    for layer in params["layers"]:
        if "router_bias" in layer:
            layer["router_bias"] = layer["router_bias"] * 10.0
    pc = mod.program_config(small, 256, dtype="float32",
                            param_dtype="float32")
    return mod, sz, params, pc


def _tokens(sz, seed, n, room=256):
    toks = np.zeros((room,), np.int32)
    toks[:n] = np.random.default_rng(seed).integers(0, sz.vocab, n)
    return toks


def test_apply_matches_the_reference_logits(tiny_ref):
    mod, sz, params, pc = tiny_ref
    assert pc.layer_types == ("conv", "conv", "full_attention", "conv")
    assert pc.expert_layers == (1, 2, 3)
    toks = _tokens(sz, 0, 100, room=128)
    model = build_model(pc)
    got = model.apply(params, jnp.asarray(toks[None, :100]))[0]
    want = mod.reference_rows(sz, params, jnp.asarray(toks), jnp.int32(0),
                              100)
    assert rel_rms(got, want) < TOL
    assert model.param_count() == mod.param_count(sz)
    loss = model.loss(params, {"tokens": jnp.asarray(toks[None, :64])})
    want_loss = mod.loss_fn(sz, params, jnp.asarray(toks[:64]))
    assert abs(float(loss) - float(want_loss)) < 1e-4
    grad = jax.grad(model.loss)(
        params, {"tokens": jnp.asarray(toks[None, :32])})
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grad))
    # the table gets the gradient of both its uses
    assert float(jnp.abs(grad["embed"]).sum()) > 0


def test_the_head_is_the_embeddings_table(tiny_ref):
    """One array: no `lm_head` leaf, `param_count` counts the table once,
    and the logits are the normed stream against the table's rows."""
    _, sz, params, pc = tiny_ref
    model = build_model(pc)
    made = model.init(jax.random.PRNGKey(0))
    assert "lm_head" not in made and "lm_head" not in params
    assert model.param_count() == sum(
        a.size for a in jax.tree.leaves(made))
    layers = sum(a.size for a in jax.tree.leaves(made["layers"]))
    assert model.param_count() == (layers + sz.vocab * sz.d_model
                                   + sz.d_model)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(5, sz.d_model)),
                    jnp.float32)
    np.testing.assert_allclose(
        model._head(params, x), x @ params["embed"].T, atol=1e-5)
    # the table is read as it lies: no transposed copy in the program
    text = str(jax.make_jaxpr(model._head)(params, x))
    assert "transpose" not in text
    # a class that ties nothing keeps its two tables
    from ray_tpu.models.parallel_hybrid import tiny_parallel_hybrid
    untied = build_model(tiny_parallel_hybrid())
    assert not untied.tied_head
    assert "lm_head" in jax.eval_shape(untied.init, jax.random.PRNGKey(0))


@pytest.mark.parametrize("p,steps", [
    (1, 6),         # shorter than the taps: both tail rows are zeros
    (2, 6),         # one real row in the tail
    (5, 8),         # shorter than a page of 8, a bucket of 16
    (20, 8),        # off a page's edge
    (33, 30),       # a bucket of 64, nearly twice the prompt
    (64, 8),        # whole pages, a bucket that is full
])
def test_prefill_then_decode_through_the_engine_matches_the_reference(
        tiny_ref, p, steps):
    mod, sz, params, pc = tiny_ref
    core = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=3)
    assert core.alloc.fixed == 1 and core.alloc.fixed_pages == 3
    toks = _tokens(sz, p, p + steps)
    got = _through_the_engine(core, toks, p, steps, lane=1)
    want = mod.reference_rows(sz, params, jnp.asarray(toks),
                              jnp.int32(p - 1), steps + 1)
    assert rel_rms(got, want) < TOL
    assert core.alloc.free_pages == core.num_pages
    # the fp8 control in the program's place fails that check
    control = mod.reference_rows(sz, params, jnp.asarray(toks),
                                 jnp.int32(p - 1), steps + 1, True)
    assert rel_rms(control, want) > 0.02


def test_the_paged_kernel_under_the_interpreter_gives_the_same_logits(
        tiny_ref, monkeypatch):
    """The same check with the paged decode kernel forced on (the Pallas
    interpreter off the TPU): two kv heads of 64 as one 128-lane, eight
    query rows."""
    mod, sz, params, pc = tiny_ref
    calls = []

    def kernel(q, *rest, **kw):
        calls.append(q.shape)
        return paged.paged_decode_attention_kernel(q, *rest, **kw)

    monkeypatch.setattr(paged, "paged_decode_attention", kernel)
    core = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
    p, steps = 21, 8
    toks = _tokens(sz, 3, p + steps)
    got = _through_the_engine(core, toks, p, steps, lane=1)
    want = mod.reference_rows(sz, params, jnp.asarray(toks),
                              jnp.int32(p - 1), steps + 1)
    assert calls == [(2, 8, 64)]        # traced once: one attention layer
    assert rel_rms(got, want) < TOL


# ------------------------------------------- faults that are this model's
def _no_b_gate(self, layer, h):
    _, C, u = jnp.split(h @ layer["w_in"], 3, axis=-1)
    return u, C


def _no_c_gate(self, layer, C, conv):
    return conv @ layer["w_out"]


def _bias_in_the_weights(x, router_w, bias, *, top_k, norm_topk_prob=True,
                         scale=1.0, **_):
    scores = jax.nn.sigmoid(x.astype(jnp.float32) @ router_w) + bias
    top_w, top_e = jax.lax.top_k(scores, top_k)
    return top_e.astype(jnp.int32), scale * top_w / jnp.sum(
        top_w, axis=-1, keepdims=True)


FAULTS = ["the B gate gone", "the C gate gone", "the head norms gone",
          "the bias left out of the choice", "the bias put into the weights",
          "norm_topk_prob off", "rope_theta 100"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_of_this_models_own_misses_the_reference(tiny_ref, fault,
                                                         monkeypatch):
    mod, sz, params, pc = tiny_ref
    toks = _tokens(sz, 1, 48, room=128)
    want = mod.reference_rows(sz, params, jnp.asarray(toks), jnp.int32(0),
                              48)
    model = GatedConvMoE(pc)
    if fault == "the B gate gone":
        monkeypatch.setattr(gcm.GatedConv, "_conv_in", _no_b_gate)
    elif fault == "the C gate gone":
        monkeypatch.setattr(gcm.GatedConv, "_conv_out", _no_c_gate)
    elif fault == "the head norms gone":
        monkeypatch.setattr(gcm, "rms_norm_reference", lambda x, w, eps: x)
    elif fault == "the bias left out of the choice":
        model = GatedConvMoE(dataclasses.replace(pc, use_expert_bias=False))
    elif fault == "the bias put into the weights":
        monkeypatch.setattr(moe, "route_topk", _bias_in_the_weights)
    elif fault == "norm_topk_prob off":
        model = GatedConvMoE(dataclasses.replace(pc, norm_topk_prob=False))
    else:
        model = GatedConvMoE(dataclasses.replace(pc, rope_theta=100.0))
    got = model.apply(params, jnp.asarray(toks[None, :48]))[0]
    # (the sound program reads 1e-6 here; the smallest fault, the bias in
    # the renormalised weights, 6e-4)
    assert rel_rms(got, want) > 2 * TOL


def test_a_tail_that_is_not_carried_misses_the_reference(tiny_ref):
    """The prefill's tails zeroed before the first decode step: the step
    convolves the new row with nothing behind it."""
    mod, sz, params, pc = tiny_ref
    core = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
    p, steps = 20, 4
    toks = _tokens(sz, 4, p + steps)
    pages = core.alloc.alloc(4)
    _, pt = _prefill(core, toks, p, pages)
    assert float(jnp.abs(core._cache["tail"][:, pages[0]]).sum()) > 0
    core._cache = {**core._cache,
                   "tail": jnp.zeros_like(core._cache["tail"])}
    got = _step(core, {0: (toks[p], p, pt)})[0]
    want = mod.reference_rows(sz, params, jnp.asarray(toks), jnp.int32(p),
                              1)[0]
    assert rel_rms(got, want) > 5 * TOL


# ------------------------------------------------ slots, pages and lanes
def test_a_reused_slot_holds_nothing_of_its_last_owners_tail(tiny_ref):
    _, sz, params, pc = tiny_ref
    r = np.random.default_rng(8)
    first, second = (r.integers(0, sz.vocab, 60) for _ in range(2))
    used = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
    _through_the_engine(used, first, 40, 20, lane=0)    # slot 0, then freed
    # a prompt of one token: its prefill must write two rows of zeros over
    # what the last owner left
    got = _through_the_engine(used, second, 1, 9, lane=1)    # slot 0 again
    fresh = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
    want = _through_the_engine(fresh, second, 1, 9, lane=1)
    np.testing.assert_array_equal(got, want)


def test_an_evicted_sequence_is_prefilled_again_to_the_same_logits(tiny_ref):
    _, sz, params, pc = tiny_ref
    r = np.random.default_rng(12)
    toks, other = r.integers(0, sz.vocab, 60), r.integers(0, sz.vocab, 60)
    kept = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
    want = _through_the_engine(kept, toks, 20, 16, lane=0)[-1]
    core = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
    _through_the_engine(core, toks, 20, 9, lane=0)          # then evicted
    _through_the_engine(core, other, 33, 12, lane=1)        # its slot reused
    got = _through_the_engine(core, toks, 29, 7, lane=1)[-1]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_eviction_and_re_prefill_give_the_same_greedy_tokens():
    cfg = tiny_gated_conv_moe()
    model = GatedConvMoE(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    # two slots and 6 more pages: the two sequences cannot both grow to 5
    # pages, the youngest is evicted, frees its slot and its pages, and is
    # prefilled again (into whichever slot is free) with what it had emitted
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


def test_an_inactive_lane_and_an_unassigned_table_write_nothing(tiny_ref):
    _, sz, params, pc = tiny_ref
    core = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=3)
    toks = np.random.default_rng(9).integers(0, sz.vocab, 40)
    pages = [core.alloc.alloc(3) for _ in range(2)]
    tables = [_prefill(core, toks[i:], 17, pages[i])[1] for i in range(2)]
    before = jax.tree.map(np.array, core._cache)
    # lane 0 runs sequence 0; sequence 1 holds its slot and no lane
    _step(core, {0: (toks[20], 17, tables[0])})
    after = jax.tree.map(np.array, core._cache)
    mine, other = pages[0][0], pages[1][0]
    was, got = before["tail"], after["tail"]    # all three convolutions'
    assert (got[:, other] == was[:, other]).all()
    assert (got[:, -1] == was[:, -1]).all()                     # nobody's
    assert all((got[li, mine] != was[li, mine]).any() for li in range(3))
    for name in ("k", "v"):             # one row, position 17
        changed = (after[name] != before[name]).any(axis=-1)
        assert changed.sum() == 1 and changed[0, pages[0][2], 1]
    assert after["moe_load"].sum() - before["moe_load"].sum() == 3 * 2
    _step(core, {})
    idle = jax.tree.map(np.array, core._cache)
    _step(core, {2: (toks[3], 5, np.full_like(tables[0], -1))})
    now = jax.tree.map(np.array, core._cache)
    assert (idle["moe_load"] == after["moe_load"]).all()
    for name in ("k", "v", "tail"):
        assert (idle[name] == after[name]).all(), name
        assert (now[name] == after[name]).all(), name


# ---------------------------------------------- what the engine is told
def test_a_slot_holds_two_rows_a_convolution_at_the_published_sizes():
    cfg = modelcfg.load_config(CONFIG)
    mod = modelcfg.load_model(cfg)
    served = build_model(mod.program_config(cfg, 4096))
    # 2 rows of 2,048 bf16 a convolution, one tile a row: 8 KB a layer
    assert conv.tail_shape(3, 2048) == (2, 16, 128)
    assert served.state_bytes() == 12 * 2 * 16 * 128 * 2 == 98304
    assert served.fixed_step_counts(2000, 16) == served.fixed_step_counts(
        9, 16) == {"state_slots": 1, "state_bytes": 2 * 98304}
    assert served.cache_page_bytes(16, fixed=True) == 98304
    # four attention layers of 8 kv heads of 64: 8,192 B a position
    assert served.cache_page_bytes(16) == 4 * 2 * 16 * 512 * 2
    assert served.fixed_pages(16) == 1
    assert served.prefill_counts(1000, 1024) == {}      # nothing scans
    assert served.param_count() == 5399129024 == mod.param_count(
        mod.sizes(cfg)) == cfg["parameters"]
    cache = jax.eval_shape(lambda: served.init_cache(16384, 16,
                                                     fixed_pages=64))
    assert {n: a.shape for n, a in cache.items() if n != "moe_step"} == {
        "k": (4, 16384, 16, 512), "v": (4, 16384, 16, 512),
        "tail": (12, 65, 2, 16, 128), "moe_load": (14, 32)}
    assert "state" not in cache
    with compute_platform("tpu"):
        assert served.decode_attention(16) == "paged_decode_attn"
        # an odd count of heads of 64 is no whole lanes of a row: gathered
        odd = build_model(dataclasses.replace(served.config, n_heads=6,
                                              n_kv_heads=3))
        assert odd.decode_attention(16) == "einsum"
    assert served.decode_attention(16) == "einsum"      # traced for a CPU
    assert served.walk_block_pages(16, 256) == 64


def test_the_engine_counts_tails_experts_and_pages_on_its_spans(
        tiny_ref, monkeypatch):
    _, _, params, pc = tiny_ref
    seen = []

    class Recorder(sp.span):
        def __init__(self, name, **attributes):
            seen.append((name, attributes))
            super().__init__(name, **attributes)

    monkeypatch.setattr(sp, "span", Recorder)
    core = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
    core.submit(list(range(1, 31)), max_tokens=6, rid="long")
    core.submit([7], max_tokens=6, rid="short")
    while core.has_work:
        core.step()
        if core._running:
            assert 0 < core.cache_stats()["fixed_pages_used"] <= 2
    c = core.counters
    per_lane = 2 * core.model.state_bytes()
    assert core.model.state_bytes() == 3 * 2 * 64 * 4   # float32 here
    assert c["state_slots_live"] == c["decode_lane_steps"] > 0
    assert c["state_bytes_moved"] == per_lane * c["state_slots_live"]
    # three expert layers, two choices a lane and step
    assert c["moe_pairs"] == 3 * 2 * c["decode_lane_steps"]
    assert 0 < c["moe_experts_touched"] <= c["moe_pairs"]
    assert "moe_zero_pairs" not in c and "moe_away_pairs" not in c
    dispatches = [a for n, a in seen if n == sp.DISPATCH]
    assert dispatches and all(
        a["state_slots"] == a["lanes"]
        and a["state_bytes"] == per_lane * a["lanes"]
        and 0 < a["live_positions"] <= a["read_positions"]
        for a in dispatches)
    prefills = {a["rid"]: a for n, a in seen if n == sp.PREFILL}
    assert prefills["long"]["tokens"] == 30
    assert prefills["long"]["bucket"] == 32
    assert prefills["short"]["tokens"] == 1
    assert all("scan_chunks" not in a for a in prefills.values())
    stats = core.cache_stats()
    assert stats["fixed_pages"] == 2 and stats["fixed_pages_used"] == 0
    assert np.asarray(stats["moe_load"]).shape == (3, 8)


def test_a_config_names_its_model_and_refusals_are_plain():
    cfg = model_config({
        "type": "gated_conv_moe", "d_model": 64,
        "layer_types": ["conv", "full_attention"], "n_heads": 8,
        "n_kv_heads": 2, "head_dim": 64, "d_ff": 96,
        "moe_intermediate_size": 32, "num_experts": 8,
        "num_experts_per_tok": 2, "num_dense_layers": 1})
    assert isinstance(cfg, GatedConvMoEConfig) and hash(cfg)
    assert isinstance(build_model(cfg), GatedConvMoE)
    assert cfg.layer_types == ("conv", "full_attention")
    assert cfg.n_layers == 2 and cfg.kv_dim == 128
    assert cfg.of_kind("conv") == (0,) and cfg.expert_layers == (1,)
    shapes = build_model(cfg).layer_shapes
    assert "w_in" in shapes(0) and "gate" in shapes(0)
    assert "q_norm" in shapes(1) and "router_bias" in shapes(1)
    assert not any(name.startswith("shared") for name in shapes(1))
    from ray_tpu.parallel.mesh import MeshSpec
    mesh = MeshSpec(dp=1, tp=2).build(jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="no mesh"):
        GatedConvMoE(tiny_gated_conv_moe(), mesh=mesh)
    with pytest.raises(ValueError, match="not built"):
        GatedConvMoEConfig(layer_types=("conv", "sliding_attention"))
    with pytest.raises(ValueError, match="kv heads"):
        GatedConvMoEConfig(n_heads=32, n_kv_heads=5)
    # the published pattern: attention at 2, 6, 10, 14, 18 and 21
    assert GatedConvMoEConfig().of_kind("full_attention") == (
        2, 6, 10, 14, 18, 21)
