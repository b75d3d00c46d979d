"""The seventh architecture (`models.hybrid_kda_moe.HybridKDAMoE`: delta-rule
layers whose decay is a vector over the key width, which hold a state of
fixed size a sequence, beside latent-attention layers, which hold one row a
position, over experts chosen under a group limit of which a share is held)
held to its plain reference (`benchmarks/models/kda_mla_moe.py`) and to
itself: the recurrence against its chunked form and both new kernels
(through the Pallas interpreter), the group limit and what it left of
`route_topk` without one, the latent attention with and without a LoRA, the
shares of an expert layer adding up to the uncut layer, prefill then decode
through the engine's own programs, a slot reused, an inactive lane,
eviction and re-prefill, a fault that no key states, and what the engine
counts and writes on its spans. Tiny sizes, CPU, seeded.
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import modelcfg                      # noqa: E402
from benchmarks.harness.reference import _ident, rel_rms     # noqa: E402
from benchmarks.harness.weights import make_weights          # noqa: E402
from ray_tpu.models import (HybridKDAMoE, HybridKDAMoEConfig,  # noqa: E402
                            MLAMoE, build_model, model_config)
from ray_tpu.models import latent, moe                       # noqa: E402
from ray_tpu.models.hybrid_kda_moe import tiny_hybrid_kda_moe  # noqa: E402
from ray_tpu.models.mla_moe import tiny_mla_moe              # noqa: E402
from ray_tpu.ops import gated_delta as gd                    # noqa: E402
from ray_tpu.ops import kda                                  # noqa: E402
from ray_tpu.ops.rope import rope_cos_sin                    # noqa: E402
from ray_tpu.serve.llm import spans as sp                    # noqa: E402
from ray_tpu.serve.llm.engine import EngineCore              # noqa: E402
from test_hybrid_delta import (_greedy, _prefill, _step,     # noqa: E402
                               _through_the_engine)
from test_llm_tracing import recorder                        # noqa: E402,F401

CONFIG = "ling-3.0-flash-vl-1chip"
PAGE = 8
H, DK, DV, C = 4, 8, 16, 8


# --------------------------------------------------- the recurrence's ops
def _case(s, seed=0, heads=H, dk=DK, dv=DV, bound=-5.0):
    """q, k (heads, s, dk) normed as a layer norms them, v, a log decay a
    key channel g in (`bound`, 0) and a beta in (0, 1)."""
    r = np.random.default_rng(seed)
    q = gd.l2_normalize(jnp.asarray(r.normal(size=(heads, s, dk)))) \
        / dk ** 0.5
    k = gd.l2_normalize(jnp.asarray(r.normal(size=(heads, s, dk))))
    v = jnp.asarray(r.normal(size=(heads, s, dv)), jnp.float32)
    g = jnp.asarray(bound * r.uniform(1e-3, 1.0, size=(heads, s, dk)),
                    jnp.float32)
    beta = jnp.asarray(r.uniform(0.05, 0.99, size=(heads, s)), jnp.float32)
    return q, k, v, g, beta


@pytest.mark.parametrize("true_len", [40, 37, 17, 5])
def test_chunk_kernel_matches_the_recurrence_and_stops_at_true_len(true_len):
    q, k, v, g, beta = _case(40)
    want_o, want_s = kda.kda_recurrence(*(
        a[:, :true_len] for a in (q, k, v, g, beta)))
    for fn in (kda.kda_prefill_kernel, kda.kda_prefill):
        o, state = fn(q, k, v, g, beta, true_len, chunk=C)
        np.testing.assert_allclose(o[:, :true_len], want_o, atol=2e-6)
        # the state is the one at true_len, not at the bucket's end
        np.testing.assert_allclose(state, want_s, atol=2e-6)
    # past the last chunk that holds the prompt the kernel writes zeros
    o, _ = kda.kda_prefill_kernel(q, k, v, g, beta, true_len, chunk=C)
    assert not np.asarray(o[:, -(-true_len // C) * C:]).any()


def test_the_plain_chunked_form_is_differentiable_and_carries_a_state():
    q, k, v, g, beta = _case(32, seed=1)
    _, mid = kda.kda_chunked(*(a[:, :16] for a in (q, k, v, g, beta)),
                             chunk=C)
    o2, end = kda.kda_chunked(*(a[:, 16:] for a in (q, k, v, g, beta)),
                              state=mid, chunk=C)
    want_o, want_s = kda.kda_recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(o2, want_o[:, 16:], atol=2e-6)
    np.testing.assert_allclose(end, want_s, atol=2e-6)
    grad = jax.grad(lambda g_: kda.kda_chunked(
        q, k, v, g_, beta, chunk=C)[0].sum())(g)
    assert np.isfinite(np.asarray(grad)).all() and np.asarray(grad).any()
    with pytest.raises(ValueError, match="whole chunks"):
        kda.kda_chunked(*(a[:, :30] for a in (q, k, v, g, beta)), chunk=C)


def test_decays_at_the_bound_for_whole_sub_blocks_stay_finite_and_right():
    """Published widths a head, chunks of 64 in sub-blocks of 16: half the
    key channels decay by the bound of -5 at every position of two whole
    sub-blocks (exp(-G) passes float32 there: 5 x 32 = 160), the others
    hardly at all, in a bucket of 128 of which 100 are real."""
    q, k, v, g, beta = _case(128, seed=5, heads=2, dk=128, dv=128)
    g = g.at[:, 16:48, :64].set(-4.999).at[:, 70:90, 64:].set(-1e-4)
    assert kda.lower_bound_fits(-5.0) and not kda.lower_bound_fits(-10.5)
    want_o, want_s = kda.kda_recurrence(*(
        a[:, :100] for a in (q, k, v, g, beta)))
    for fn in (kda.kda_prefill_kernel, kda.kda_prefill):
        o, state = fn(q, k, v, g, beta, 100, chunk=64)
        assert np.isfinite(np.asarray(o)).all()
        np.testing.assert_allclose(o[:, :100], want_o, atol=2e-6)
        np.testing.assert_allclose(state, want_s, atol=5e-6)


def test_a_vector_decay_is_not_its_mean_over_the_key_width():
    """What tells this rule from `ops.gated_delta`'s: with every head's
    decay replaced by its mean over the key width the outputs differ."""
    q, k, v, g, beta = _case(24, seed=6)
    want, _ = kda.kda_recurrence(q, k, v, g, beta)
    flat, _ = gd.gated_delta_recurrence(q, k, v, g.mean(-1), beta)
    same, _ = kda.kda_recurrence(
        q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta)
    np.testing.assert_allclose(flat, same, atol=2e-6)
    assert rel_rms(flat, want) > 0.05


def test_step_kernel_matches_the_recurrence_and_writes_active_slots_only():
    q, k, v, g, beta = (a[:, :3].swapaxes(0, 1) for a in _case(3, seed=2))
    pool = np.random.default_rng(3).normal(
        size=(2, 5, DK, H * DV)).astype(np.float32)
    slots = jnp.asarray([2, -1, 0], jnp.int32)
    for fn in (kda.kda_step_kernel, kda.kda_step_reference):
        o, new = fn(q, k, v, g, beta, jnp.asarray(pool), 1, slots)
        new = np.asarray(new)
        for lane, slot in ((0, 2), (2, 0)):
            S0 = pool[1, slot].reshape(DK, H, DV).transpose(1, 0, 2)
            want_o, want_s = kda.kda_recurrence(*(
                a[lane][:, None] for a in (q, k, v, g, beta)),
                state=jnp.asarray(S0))
            np.testing.assert_allclose(o[lane], want_o[:, 0], atol=2e-6)
            np.testing.assert_allclose(
                new[1, slot].reshape(DK, H, DV).transpose(1, 0, 2), want_s,
                atol=2e-6)
        # the other layer, the slots of no lane and nobody's: bit for bit
        assert (new[0] == pool[0]).all()
        assert (new[1, [1, 3, 4]] == pool[1, [1, 3, 4]]).all()


def test_kernels_tile_the_published_shapes_and_say_where_they_run():
    from ray_tpu.ops.dispatch import compute_platform
    assert not kda.uses_step_kernel(32, 128, 128)       # traced for the CPU
    with compute_platform("tpu"):
        assert kda.uses_step_kernel(32, 128, 128)
        assert kda.uses_chunk_kernel(128, 128, 64, jnp.bfloat16)
        assert not kda.uses_chunk_kernel(128, 128, 60, jnp.bfloat16)
    # 16 heads' columns a grid step: 1 MiB of float32 state
    assert gd.step_columns(32, 128, 128) == 2048
    assert gd.chunk_heads(32) == 4


# ------------------------------------------------------- the group limit
def _route_case(seed, T=40, d=32, slots=16):
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.normal(size=(T, d)), jnp.float32),
            jnp.asarray(r.normal(size=(d, slots)) * 0.5, jnp.float32),
            jnp.asarray(r.normal(size=(slots,)) * 0.1, jnp.float32))


def test_the_group_limit_keeps_the_choice_inside_the_best_groups():
    x, w, bias = _route_case(0)
    top_e, top_w = moe.route_topk(x, w, bias, top_k=4, scale=2.5, n_group=4,
                                  topk_group=2)
    scores = jax.nn.sigmoid(x @ w)
    choice = np.asarray(scores + bias)
    for t in range(x.shape[0]):
        groups = np.sort(choice[t].reshape(4, 4), axis=-1)
        kept = np.argsort(-(groups[:, -1] + groups[:, -2]))[:2]
        allowed = [e for e in range(16) if e // 4 in kept]
        want = sorted(allowed, key=lambda e: -choice[t, e])[:4]
        assert sorted(np.asarray(top_e[t]).tolist()) == sorted(want)
        assert {int(e) // 4 for e in top_e[t]} <= set(kept.tolist())
    # the weights are the scores' own, normalised and scaled
    picked = np.take_along_axis(np.asarray(scores), np.asarray(top_e), -1)
    np.testing.assert_allclose(
        top_w, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    # without the limit some token chooses outside its two best groups
    free, _ = moe.route_topk(x, w, bias, top_k=4, scale=2.5)
    assert (np.sort(np.asarray(free)) != np.sort(np.asarray(top_e))).any()
    # all the groups kept is no limit
    every, _ = moe.route_topk(x, w, bias, top_k=4, scale=2.5, n_group=4,
                              topk_group=4)
    np.testing.assert_array_equal(every, free)
    with pytest.raises(ValueError, match="groups"):
        moe.route_topk(x, w, bias, top_k=4, n_group=3, topk_group=2)


def _route_topk_before(x, router_w, bias, *, top_k, norm_topk_prob=True,
                       scale=1.0, scoring="sigmoid"):
    """`moe.route_topk` as PR 49 left it."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = moe.SCORING[scoring](logits)
    _, top_e = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if norm_topk_prob:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    return top_e.astype(jnp.int32), top_w * scale


@pytest.mark.parametrize("how", [
    dict(top_k=2), dict(top_k=4, scale=1.8),
    dict(top_k=3, norm_topk_prob=False, scoring="softmax", scale=6.0)])
def test_one_group_routes_bit_for_bit_as_it_did(how):
    x, w, bias = _route_case(1)
    want = _route_topk_before(x, w, bias, **how)
    for got in (moe.route_topk(x, w, bias, **how),
                moe.route_topk(x, w, bias, n_group=1, topk_group=1, **how)):
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
    # and traces the same program
    text = lambda fn: str(jax.make_jaxpr(                       # noqa: E731
        lambda *a: fn(*a, **how))(x, w, bias))
    assert text(moe.route_topk) == text(_route_topk_before)


# ------------------------------------------- the latent attention's forms
def test_a_lora_query_without_a_gate_has_the_leaves_it_had():
    c = tiny_mla_moe()
    assert list(latent.LatentAttention(c).shapes(0.02, 0.01)) == [
        "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo"]
    assert not c.head_gate
    mine = tiny_hybrid_kda_moe()
    assert list(latent.LatentAttention(mine).shapes(0.02, 0.01)) == [
        "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "w_head_gate"]


def test_the_absorbed_form_is_the_expanded_form_with_the_gate():
    """One latent layer of the tiny config: a sequence through the expanded
    form (prefill's), then its last position through the absorbed form over
    the rows the first wrote (decode's), gate and all; and the gate is
    something (without it the output differs)."""
    cfg = tiny_hybrid_kda_moe()
    model = HybridKDAMoE(cfg)
    layer = jax.tree.map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.PRNGKey(a.size),
                                              a.shape),
        model.init(jax.random.PRNGKey(0))["layers"][3])
    s = 24
    h = jax.random.normal(jax.random.PRNGKey(1), (1, s, cfg.d_model))
    cos, sin = rope_cos_sin(jnp.arange(s)[None], cfg.qk_rope_head_dim,
                            cfg.rope_theta)
    out, c_kv, k_rope = model.attention._attn_expanded(layer, h, cos, sin)
    pool = jnp.zeros((1, 4, PAGE, cfg.row_width))
    ids = jnp.asarray([2, 0, 3])
    pool = model.attention._write_pages(
        pool, 0, c_kv[0, :s - 1], k_rope[0, :s - 1], ids, PAGE)
    tables = jnp.asarray([[2, 0, 3, -1]])
    cos1, sin1 = rope_cos_sin(jnp.asarray([s - 1]), cfg.qk_rope_head_dim,
                              cfg.rope_theta)
    got, _ = model.attention._attn_absorbed(
        layer, h[0, -1:], cos1, sin1, pool, 0, jnp.asarray([3]),
        jnp.asarray([(s - 1) % PAGE]), tables, jnp.asarray([s]))
    np.testing.assert_allclose(got[0], out[0, -1], atol=2e-5)
    plain = HybridKDAMoE(dataclasses.replace(cfg, head_gate=False))
    bare, _, _ = plain.attention._attn_expanded(layer, h, cos, sin)
    assert rel_rms(bare, out) > 0.1


def test_latent_attention_with_a_lora_gives_what_it_gave():
    """`MLAMoE` at its tiny size: the query through its LoRA, no gate; the
    expanded form against the function as PR 49 left it."""
    cfg = tiny_mla_moe()
    model = MLAMoE(cfg)
    layer = model.init(jax.random.PRNGKey(0))["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    cos, sin = rope_cos_sin(jnp.broadcast_to(jnp.arange(16), (2, 16)),
                            cfg.qk_rope_head_dim, cfg.rope_theta)
    got, _, _ = model.attention._attn_expanded(layer, h, cos, sin)

    def q_before(layer, h):
        c_q = latent.rms_norm_reference(h @ layer["wq_a"], layer["q_norm"],
                                        cfg.norm_eps)
        return (c_q @ layer["wq_b"]).reshape(2, 16, cfg.n_heads,
                                             cfg.qk_head_dim)

    np.testing.assert_array_equal(model.attention._q(layer, h),
                                  q_before(layer, h))
    assert got.shape == (2, 16, cfg.n_heads * cfg.v_head_dim)


# ------------------------------------------------------- against the reference
@pytest.fixture(scope="module")
def tiny_ref():
    """(model module, its Sizes at the tiny size, seeded float32 weights,
    the program's config for them): a dense KDA layer, two KDA layers and a
    latent one over experts 4..7 of 16, chosen among 2 of 4 groups."""
    cfg = modelcfg.load_config(CONFIG)
    mod = modelcfg.load_model(cfg)
    small = mod.tiny(cfg)
    sz = mod.sizes(small)
    params = make_weights(mod.weight_shapes(sz), 11, dtype=jnp.float32)
    # a bias that moves choices, so that choosing by the score alone fails
    for layer in params["layers"]:
        if "router_bias" in layer:
            layer["router_bias"] = layer["router_bias"] * 100.0
    pc = mod.program_config(small, 256, dtype="float32",
                            param_dtype="float32")
    return mod, sz, params, pc


def test_apply_matches_the_reference_logits(tiny_ref):
    mod, sz, params, pc = tiny_ref
    assert sz.layer_types == ("linear_attention",) * 3 + (
        "latent_attention",) and sz.mlp_types == ("dense", "E", "E", "E")
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


@pytest.mark.parametrize("p,steps", [
    (5, 8),         # shorter than a chunk of 8, in a bucket of 16
    (20, 8),        # not whole chunks; the tail's last 3 real inputs
    (33, 30),       # a bucket of 64, nearly twice the prompt
    (64, 8),        # whole chunks, a bucket that is full
])
def test_prefill_then_decode_through_the_engine_matches_the_reference(
        tiny_ref, p, steps):
    mod, sz, params, pc = tiny_ref
    core = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=3)
    assert core.alloc.fixed == 1 and core.alloc.fixed_pages == 3
    toks = np.zeros((256,), np.int32)
    toks[:p + steps] = np.random.default_rng(p).integers(0, sz.vocab,
                                                         p + steps)
    got = _through_the_engine(core, toks, p, steps, lane=1)
    want = mod.reference_rows(sz, params, jnp.asarray(toks),
                              jnp.int32(p - 1), steps + 1)
    assert rel_rms(got, want) < 2e-4
    assert core.alloc.free_pages == core.num_pages
    # the fp8 control in the program's place fails that check
    control = mod.reference_rows(sz, params, jnp.asarray(toks),
                                 jnp.int32(p - 1), steps + 1, True)
    assert rel_rms(control, want) > 0.02


def test_the_kernels_under_the_interpreter_give_the_same_logits(
        tiny_ref, monkeypatch):
    """The same check with the two new kernels of the served path forced on
    (the Pallas interpreter off the TPU): the chunked scan and the step."""
    mod, sz, params, pc = tiny_ref
    monkeypatch.setattr(kda, "kda_prefill", kda.kda_prefill_kernel)
    monkeypatch.setattr(kda, "kda_step", kda.kda_step_kernel)
    core = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
    p, steps = 21, 8
    toks = np.zeros((256,), np.int32)
    toks[:p + steps] = np.random.default_rng(3).integers(0, sz.vocab,
                                                         p + steps)
    got = _through_the_engine(core, toks, p, steps, lane=1)
    want = mod.reference_rows(sz, params, jnp.asarray(toks),
                              jnp.int32(p - 1), steps + 1)
    assert rel_rms(got, want) < 2e-4


def test_one_decay_a_head_under_this_models_name_misses_the_reference(
        tiny_ref, monkeypatch):
    """A fault that no key of the config states: every head's decay
    replaced by its mean over the key width (the gated delta rule of
    `ops.gated_delta` under this model's name). The same check must miss
    by far more than its tolerance."""
    mod, sz, params, pc = tiny_ref
    sound = kda.gates

    def one_number_a_head(f, b, a_log, dt_bias, lower_bound):
        g, beta = sound(f, b, a_log, dt_bias, lower_bound)
        return jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta

    monkeypatch.setattr(kda, "gates", one_number_a_head)
    core = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
    p, steps = 33, 16
    toks = np.zeros((256,), np.int32)
    toks[:p + steps] = np.random.default_rng(4).integers(0, sz.vocab,
                                                         p + steps)
    got = _through_the_engine(core, toks, p, steps, lane=0)
    want = mod.reference_rows(sz, params, jnp.asarray(toks),
                              jnp.int32(p - 1), steps + 1)
    assert rel_rms(got, want) > 50 * 2e-4


def test_a_reused_slot_gives_what_a_fresh_engine_gives(tiny_ref):
    _, sz, params, pc = tiny_ref
    r = np.random.default_rng(8)
    first, second = (r.integers(0, sz.vocab, 60) for _ in range(2))
    used = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
    _through_the_engine(used, first, 40, 20, lane=0)    # slot 0, then freed
    got = _through_the_engine(used, second, 11, 9, lane=1)   # slot 0 again
    fresh = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
    want = _through_the_engine(fresh, second, 11, 9, lane=1)
    np.testing.assert_array_equal(got, want)


def test_an_inactive_lane_and_an_unassigned_table_write_nothing(tiny_ref):
    _, sz, params, pc = tiny_ref
    core = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=3)
    toks = np.random.default_rng(9).integers(0, sz.vocab, 40)
    pages = [core.alloc.alloc(3) for _ in range(2)]
    tables = [_prefill(core, toks[i:], 17, pages[i])[1] for i in range(2)]
    # copies: the cache is donated to the next step, its buffers reused
    before = jax.tree.map(np.array, core._cache)
    # lane 0 runs sequence 0; sequence 1 holds its slot and no lane
    _step(core, {0: (toks[20], 17, tables[0])})
    after = jax.tree.map(np.array, core._cache)
    mine, other = pages[0][0], pages[1][0]
    for name in ("state", "tail"):
        assert (after[name][:, other] == before[name][:, other]).all()
        assert (after[name][:, -1] == before[name][:, -1]).all()  # nobody's
        assert (after[name][:, mine] != before[name][:, mine]).any()
    # the latent row of position 17: page 2 of sequence 0, row 1, alone
    changed = np.argwhere((after["kv"] != before["kv"]).any(-1))
    assert changed.tolist() == [[0, pages[0][2], 1]]
    # a step of no active lane, and of a lane whose table is unassigned
    # (-1 everywhere), leaves every pool bit for bit as it was
    _step(core, {})
    idle = jax.tree.map(np.array, core._cache)
    assert (idle["moe_load"] == after["moe_load"]).all()    # no pair
    assert not any(int(n) for n in core.model.step_stats(
        core._cache).values())
    _step(core, {2: (toks[3], 5, np.full_like(tables[0], -1))})
    now = jax.tree.map(np.array, core._cache)
    for name in ("kv", "state", "tail"):
        assert (idle[name] == after[name]).all(), name
        assert (now[name] == after[name]).all(), name


def test_eviction_and_re_prefill_give_the_same_greedy_tokens():
    cfg = tiny_hybrid_kda_moe()
    model = HybridKDAMoE(cfg)
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


def test_an_evicted_sequence_is_prefilled_again_to_the_same_logits(tiny_ref):
    """What eviction does to a sequence, by hand: its pages and slot freed,
    another sequence run over them, then prompt and emitted tokens
    prefilled again into whatever is free: the next step's logits are
    those of the sequence that was never evicted."""
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


def test_a_state_costs_a_sequence_the_same_at_any_length():
    cfg = modelcfg.load_config(CONFIG)
    mod = modelcfg.load_model(cfg)
    served = build_model(mod.program_config(cfg, 16384))
    # 128 x 4096 float32 of state and 3 x 12288 bf16 of tail a layer
    assert served.state_bytes() == 6 * (128 * 4096 * 4 + 3 * 12288 * 2)
    assert served.fixed_step_counts(12000, 16) == served.fixed_step_counts(
        9, 16) == {"state_slots": 1, "state_bytes": 2 * served.state_bytes()}
    assert served.cache_page_bytes(16, fixed=True) == served.state_bytes()
    # one latent layer: a row of 576 numbers padded to 640, 1,280 B
    assert served.cache_page_bytes(16) == 16 * 640 * 2
    assert served.fixed_pages(16) == 1 and served.pool_rows == 1
    assert served.prefill_counts(1000, 1024) == {"scan_chunks": 16}
    assert served.param_count() == 5231790016 == mod.param_count(
        mod.sizes(cfg)) == cfg["parameters"]


def test_the_engine_counts_state_and_experts_and_writes_them_on_its_spans(
        tiny_ref, monkeypatch):
    _, sz, params, pc = tiny_ref
    seen = []

    class Recorder(sp.span):
        def __init__(self, name, **attributes):
            seen.append((name, attributes))
            super().__init__(name, **attributes)

    monkeypatch.setattr(sp, "span", Recorder)
    core = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
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
    dispatches = [a for n, a in seen if n == sp.DISPATCH]
    assert dispatches and all(
        a["state_slots"] == a["lanes"]
        and a["state_bytes"] == per_lane * a["lanes"] for a in dispatches)
    # the latent layer's rows are counted as a latent model's are: what the
    # lanes hold, and (the gather off the TPU) every table's positions
    assert c["kv_positions_live"] == sum(
        a["live_positions"] for a in dispatches) > 0
    assert all(a["read_positions"] == core._table_positions
               for a in dispatches)
    prefills = {a["rid"]: a for n, a in seen if n == sp.PREFILL}
    assert prefills["long"]["scan_chunks"] == 4         # 30 tokens, C = 8
    assert prefills["long"]["tokens"] == 30
    assert prefills["long"]["bucket"] == 32
    assert prefills["short"]["scan_chunks"] == 1
    # every choice of every lane-step is held here or away: top_k x the
    # expert layers a lane-step; no slot computes nothing
    assert c["moe_pairs"] + c["moe_away_pairs"] == (
        sz.top_k * len(sz.of_kind("E")) * c["decode_lane_steps"])
    assert c["moe_zero_pairs"] == 0 and c["moe_pairs"] > 0
    emits = [a for n, a in seen if n == sp.EMIT]
    assert sum(a.get("moe_pairs", 0) for a in emits) == c["moe_pairs"]
    stats = core.cache_stats()
    assert stats["fixed_pages_used"] == 0
    load = np.asarray(stats["moe_load"])
    assert load.shape == (3, sz.held) and load.sum() == c["moe_pairs"]


def test_the_set_up_span_names_the_pools_shapes(tiny_ref, recorder):  # noqa
    _, sz, params, pc = tiny_ref
    EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
    spans = {e[4]: e[7] for e in recorder.snapshot()
             if e[4] == sp.SETUP_CACHE and e[7]}
    assert spans[sp.SETUP_CACHE]["fixed_pages"] == 2
    assert spans[sp.SETUP_CACHE]["pool_rows"] == 1
    # one latent layer's rows of 128; three KDA layers' slots (2 and
    # nobody's), a state of 8 x 32 and a tail of 3 x 96
    assert spans[sp.SETUP_CACHE]["pools"] == (
        "kv:1x40x8x128 state:3x3x8x32 tail:3x3x3x1x96")


def test_a_config_names_its_model_and_refusals_are_plain():
    cfg = model_config({
        "type": "hybrid_kda_moe", "d_model": 64,
        "layer_types": ["linear_attention", "latent_attention"],
        "mlp_layer_types": ["dense", "sparse"], "n_heads": 4,
        "linear_key_dim": 8, "linear_value_dim": 16, "chunk": 8,
        "kv_lora_rank": 96, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
        "v_head_dim": 32, "d_ff": 128, "moe_intermediate_size": 32,
        "shared_intermediate_size": 32, "n_routed_experts": 8,
        "num_experts_per_tok": 2, "n_group": 2, "topk_group": 1})
    assert isinstance(cfg, HybridKDAMoEConfig) and hash(cfg)
    assert isinstance(build_model(cfg), HybridKDAMoE)
    assert cfg.of_kind("linear_attention") == (0,) == cfg.of_kind("dense")
    assert cfg.of_kind("latent_attention") == (1,) == cfg.of_kind("sparse")
    assert cfg.held == (0, 8) and cfg.q_lora_rank is None and cfg.head_gate
    assert cfg.conv_channels == 2 * 32 + 64 and cfg.row_width == 128
    from ray_tpu.parallel.mesh import MeshSpec
    mesh = MeshSpec(dp=1, tp=2).build(jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="no mesh"):
        HybridKDAMoE(tiny_hybrid_kda_moe(), mesh=mesh)
    with pytest.raises(ValueError, match="not built"):
        HybridKDAMoEConfig(layer_types=("full_attention",) * 6)
    with pytest.raises(ValueError, match="unlike numbers"):
        HybridKDAMoEConfig(mlp_layer_types=("sparse",))
    with pytest.raises(ValueError, match="experts_held"):
        HybridKDAMoEConfig(experts_held=(500, 128))
    with pytest.raises(ValueError, match="kda_lower_bound"):
        HybridKDAMoEConfig(kda_lower_bound=-20.0)
    # a model without linear layers keeps nothing of a sequence for ever
    assert HybridKDAMoE(dataclasses.replace(
        tiny_hybrid_kda_moe(), layer_types=("latent_attention",) * 4)
    ).fixed_pages(16) == 0


# ------------------------------------------------------- the shares add up
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(tiny_ref):
    """Four shares of 4 of 16 experts, chosen among 2 of 4 groups over all
    16: the held parts of all four plus the shared expert counted once
    equal the uncut reference's layer; in the reference and in the program
    alike."""
    mod, sz, _, pc = tiny_ref
    whole = dataclasses.replace(sz, first_held=0, held=sz.experts)
    layer = make_weights(mod.weight_shapes(whole), 5,
                         dtype=jnp.float32)["layers"][1]
    layer["router_bias"] = layer["router_bias"] * 100.0
    u = jnp.asarray(np.random.default_rng(1).normal(size=(24, sz.d_model)),
                    jnp.float32)
    shared = mod.shared_part(whole, u, layer, _ident)
    want = mod.held_part(whole, u, layer, _ident) + shared
    assert rel_rms(shared, want) > 0.1          # the routed part matters
    ref_parts, got_parts = [], []
    for first in range(0, sz.experts, 4):
        share = dataclasses.replace(sz, first_held=first, held=4)
        mine = {**layer, **{k: layer[k][first:first + 4]
                            for k in ("moe_gate", "moe_up", "moe_down")}}
        ref_parts.append(mod.held_part(share, u, mine, _ident))
        program = HybridKDAMoE(dataclasses.replace(
            pc, experts_held=(first, 4)))
        out, counts = program._ffn(mine, u)
        got_parts.append(out - shared)
        assert int(counts["pairs"]) + int(counts["away_pairs"]) == (
            24 * sz.top_k)
    assert rel_rms(sum(ref_parts) + shared, want) < 1e-5
    assert rel_rms(sum(got_parts) + shared, want) < 1e-5
    # and no share is nothing: each holds something a token chose
    assert all(float(jnp.abs(p).max()) > 0 for p in ref_parts)
    # without the group limit the uncut layer is another layer
    free = dataclasses.replace(whole, n_group=1, topk_group=1)
    assert rel_rms(mod.held_part(free, u, layer, _ident) + shared,
                   want) > 0.05
