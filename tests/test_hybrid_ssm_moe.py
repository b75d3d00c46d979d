"""The sixth architecture (`models.hybrid_ssm_moe.HybridSSMMoE`: state-space
layers that hold a state of fixed size a sequence, experts of two matrices
in a latent of which a share is held, and grouped-query attention layers
that hold pages, behind one page table) held to its plain reference
(`benchmarks/models/nemotron_h.py`) and to itself: prefill then decode
through the engine's own programs, the kernels under the interpreter, the
fp8 control failing the same check, a slot reused, an inactive lane, the
shares of an expert layer adding up to the uncut layer, `dropless_moe_ffn`'s
two new arguments, and what the engine counts and writes on its spans. Tiny
sizes, CPU, seeded.
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
from benchmarks.harness.reference import _ident, rel_rms     # noqa: E402
from benchmarks.harness.weights import make_weights          # noqa: E402
from ray_tpu.models import (HybridSSMMoE, HybridSSMMoEConfig,  # noqa: E402
                            build_model, model_config)
from ray_tpu.models import moe                               # noqa: E402
from ray_tpu.models.hybrid_ssm_moe import tiny_hybrid_ssm_moe  # noqa: E402
from ray_tpu.ops import grouped_matmul as gmm                # noqa: E402
from ray_tpu.ops import paged_attention as paged             # noqa: E402
from ray_tpu.ops import ssd                                  # noqa: E402
from ray_tpu.serve.llm import spans as sp                    # noqa: E402
from ray_tpu.serve.llm.engine import EngineCore              # noqa: E402
from test_hybrid_delta import (_prefill, _step,              # noqa: E402
                               _through_the_engine)
from test_llm_tracing import recorder                        # noqa: E402,F401

CONFIG = "nemotron-3-super-120b-a12b-1chip"
PAGE = 8


@pytest.fixture(scope="module")
def tiny_ref():
    """(model module, its Sizes at the tiny size, seeded float32 weights,
    the program's config for them): MEM*E, 4 state-space heads of 8 in 2
    groups, 4 query heads over 2 kv heads, experts 4..7 of 16 held."""
    cfg = modelcfg.load_config(CONFIG)
    mod = modelcfg.load_model(cfg)
    small = mod.tiny(cfg)
    sz = mod.sizes(small)
    params = make_weights(mod.weight_shapes(sz), 11, dtype=jnp.float32)
    # a bias that moves choices, so that choosing by the score alone fails
    # and experts that add as much as the shared expert does: behind a
    # squared ReLU the seeded std of 0.02 leaves them 0.3 % of it here
    for layer in params["layers"]:
        if "router_bias" in layer:
            layer["router_bias"] = layer["router_bias"] * 400.0
            layer["moe_up"] = layer["moe_up"] * 12.0
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
    """The same check with the two new kernels of the served path forced
    on (the Pallas interpreter off the TPU): the chunked scan and the
    step. (The paged decode attention and the grouped matmul tile no shape
    this small; the first is held at this family's grouping below.)"""
    mod, sz, params, pc = tiny_ref
    monkeypatch.setattr(ssd, "ssd_prefill", ssd.ssd_prefill_kernel)
    monkeypatch.setattr(ssd, "ssd_step", ssd.ssd_step_kernel)
    core = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
    p, steps = 21, 8
    toks = np.zeros((256,), np.int32)
    toks[:p + steps] = np.random.default_rng(3).integers(0, sz.vocab,
                                                         p + steps)
    got = _through_the_engine(core, toks, p, steps, lane=1)
    want = mod.reference_rows(sz, params, jnp.asarray(toks),
                              jnp.int32(p - 1), steps + 1)
    assert rel_rms(got, want) < 2e-4


def test_paged_decode_kernel_at_sixteen_query_heads_a_kv_head():
    """32 query heads over 2 kv heads of 128, the published grouping,
    through the interpreter against the gathered reference."""
    r = np.random.default_rng(0)
    lengths = jnp.asarray([40, 1, 0, 17], jnp.int32)
    B, heads, kv, hd, page, pages = 4, 32, 2, 128, 16, 12
    q = jnp.asarray(r.normal(size=(B, heads, hd)), jnp.float32)
    k_pool, v_pool = (jnp.asarray(r.normal(size=(1, pages, page, kv * hd)),
                                  jnp.float32) for _ in range(2))
    tables = jnp.asarray(r.permutation(pages).reshape(B, 3), jnp.int32)
    got = paged.paged_decode_attention_kernel(q, k_pool, v_pool, 0, tables,
                                              lengths)
    want = paged.paged_attention_reference(q, k_pool, v_pool, 0, tables,
                                           lengths)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5)


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
    # a step of no active lane, and of a lane whose table is unassigned
    # (-1 everywhere), leaves every pool bit for bit as it was
    _step(core, {})
    idle = jax.tree.map(np.array, core._cache)
    assert (idle["moe_load"] == after["moe_load"]).all()    # no pair
    assert not any(int(n) for n in core.model.step_stats(
        core._cache).values())
    _step(core, {2: (toks[3], 5, np.full_like(tables[0], -1))})
    now = jax.tree.map(np.array, core._cache)
    for name in ("k", "v", "state", "tail"):
        assert (idle[name] == after[name]).all(), name
        assert (now[name] == after[name]).all(), name


def test_a_state_costs_a_sequence_the_same_at_any_length():
    cfg = modelcfg.load_config(CONFIG)
    mod = modelcfg.load_model(cfg)
    served = build_model(mod.program_config(cfg, 8192))
    # 128 x 8192 float32 of state and 3 x 10240 bf16 of tail a layer
    assert served.state_bytes() == 5 * (128 * 8192 * 4 + 3 * 10240 * 2)
    assert served.fixed_step_counts(7000, 16) == served.fixed_step_counts(
        9, 16) == {"state_slots": 1, "state_bytes": 2 * served.state_bytes()}
    assert served.cache_page_bytes(16, fixed=True) == served.state_bytes()
    # one attention layer of 2 kv heads of 128: 1 KB a position
    assert served.cache_page_bytes(16) == 2 * 16 * 256 * 2
    assert served.fixed_pages(16) == 1
    assert served.param_count() == 4648163712 == mod.param_count(
        mod.sizes(cfg))


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
    assert load.shape == (2, sz.held) and load.sum() == c["moe_pairs"]


def test_the_set_up_span_names_the_pools_shapes(tiny_ref, recorder):  # noqa
    _, sz, params, pc = tiny_ref
    EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
    spans = {e[4]: e[7] for e in recorder.snapshot()
             if e[4] == sp.SETUP_CACHE and e[7]}
    assert spans[sp.SETUP_CACHE]["fixed_pages"] == 2
    # one attention layer's pages; two state-space layers' slots (2 and
    # nobody's), a state of 16 x 32 and a tail of 3 x 96
    assert spans[sp.SETUP_CACHE]["pools"] == (
        "k:1x40x8x32 state:2x3x16x32 tail:2x3x3x1x96 v:1x40x8x32")


def test_a_config_names_its_model_and_refusals_are_plain():
    cfg = model_config({
        "type": "hybrid_ssm_moe", "d_model": 64, "layer_types": "M*E",
        "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "ssm_heads": 4,
        "ssm_head_dim": 8, "ssm_groups": 2, "ssm_state": 16, "chunk": 8,
        "moe_latent_size": 32, "moe_intermediate_size": 48,
        "shared_intermediate_size": 96, "n_routed_experts": 8,
        "num_experts_per_tok": 2})
    assert isinstance(cfg, HybridSSMMoEConfig) and hash(cfg)
    assert isinstance(build_model(cfg), HybridSSMMoE)
    assert cfg.layer_types == ("M", "*", "E") and cfg.held == (0, 8)
    assert cfg.of_kind("M") == (0,) and cfg.of_kind("E") == (2,)
    assert cfg.ssm_inner == 32 and cfg.conv_channels == 32 + 2 * 32
    from ray_tpu.parallel.mesh import MeshSpec
    mesh = MeshSpec(dp=1, tp=2).build(jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="no mesh"):
        HybridSSMMoE(tiny_hybrid_ssm_moe(), mesh=mesh)
    with pytest.raises(ValueError, match="not built"):
        HybridSSMMoEConfig(layer_types="M-E")
    with pytest.raises(ValueError, match="experts_held"):
        HybridSSMMoEConfig(experts_held=(500, 128))
    # a model without state-space layers keeps nothing of a sequence for
    # ever
    assert HybridSSMMoE(dataclasses.replace(
        tiny_hybrid_ssm_moe(), layer_types="*E")).fixed_pages(16) == 0


# ------------------------------------------------------- the shares add up
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(tiny_ref):
    """Four shares of 4 of 16 experts: the held parts of all four, each
    through `W_fc2`, plus the shared expert counted once, equal the uncut
    reference's `MoE(u)`; in the reference and in the program alike."""
    mod, sz, _, pc = tiny_ref
    whole = dataclasses.replace(sz, first_held=0, held=sz.experts)
    layer = make_weights(mod.weight_shapes(whole), 5,
                         dtype=jnp.float32)["layers"][1]
    layer["router_bias"] = layer["router_bias"] * 400.0
    layer["moe_up"] = layer["moe_up"] * 12.0
    u = jnp.asarray(np.random.default_rng(1).normal(size=(24, sz.d_model)),
                    jnp.float32)
    want = mod._experts(whole, u, layer, _ident)
    shared = mod.shared_part(whole, u, layer, _ident)
    assert rel_rms(shared, want) > 0.1          # the routed part matters
    ref_parts, got_parts = [], []
    for first in range(0, sz.experts, 4):
        share = dataclasses.replace(sz, first_held=first, held=4)
        mine = {**layer, "moe_up": layer["moe_up"][first:first + 4],
                "moe_down": layer["moe_down"][first:first + 4]}
        ref_parts.append(mod.held_part(share, u, mine, _ident))
        program = HybridSSMMoE(dataclasses.replace(
            pc, experts_held=(first, 4)))
        out, counts = program._ffn(mine, u)
        got_parts.append(out - shared)
        assert int(counts["pairs"]) + int(counts["away_pairs"]) == (
            24 * sz.top_k)
    assert rel_rms(sum(ref_parts) + shared, want) < 1e-5
    assert rel_rms(sum(got_parts) + shared, want) < 1e-5
    # and no share is nothing: each holds something a token chose
    assert all(float(jnp.abs(p).max()) > 0 for p in ref_parts)


# ------------------------------------ dropless_moe_ffn's two new arguments
def _moe_case(seed, T=20, d=32, f=48, E=8, lat=None):
    r = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(r.normal(size=s) * 0.3, jnp.float32)  # noqa
    dl = lat or d
    return {"x": mk(T, d), "router": mk(d, E), "bias": mk(E) * 0.1,
            "gate": mk(E, dl, f), "up": mk(E, dl, f), "down": mk(E, f, dl),
            "fc1": mk(d, dl)}


def _plain(case, top_k, form, z=None, **route):
    """The layer written out expert by expert."""
    x = case["x"]
    z = x if z is None else z
    top_e, top_w = moe.route_topk(x, case["router"], case["bias"],
                                  top_k=top_k, **route)
    y = jnp.zeros(z.shape, jnp.float32)
    for e in range(case["router"].shape[1]):
        w = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        if form == "relu2":
            out = jnp.square(jax.nn.relu(z @ case["up"][e])) @ case[
                "down"][e]
        else:
            out = (jax.nn.silu(z @ case["gate"][e]) * (z @ case["up"][e])
                   ) @ case["down"][e]
        y = y + w[:, None] * out
    return y


def test_a_two_matrix_relu2_expert_against_its_plain_form():
    case = _moe_case(0)
    got, counts = moe.dropless_moe_ffn(
        case["x"], case["router"], case["bias"], None, case["up"],
        case["down"], top_k=3, scale=2.5, expert_form="relu2")
    np.testing.assert_allclose(got, _plain(case, 3, "relu2", scale=2.5),
                               atol=2e-5)
    assert int(counts["pairs"]) == 20 * 3
    with pytest.raises(ValueError, match="expert form"):
        moe.dropless_moe_ffn(case["x"], case["router"], case["bias"], None,
                             case["up"], case["down"], top_k=3,
                             expert_form="geglu")


@pytest.mark.parametrize("form", ["relu2", "swiglu"])
def test_an_expert_input_narrower_than_the_routers(form):
    """The router reads x (32 wide), the experts a latent of 16: the
    result is 16 wide, a share of the experts held, padding given no
    pair."""
    case = _moe_case(1, lat=16)
    z = case["x"] @ case["fc1"]
    valid = jnp.arange(20) < 17
    held = (2, 4)
    got, counts = moe.dropless_moe_ffn(
        case["x"], case["router"], case["bias"],
        None if form == "relu2" else case["gate"][2:6], case["up"][2:6],
        case["down"][2:6], top_k=3, valid=valid, held=held,
        expert_form=form, expert_input=z)
    assert got.shape == (20, 16)
    mine = dict(case)
    for name in ("gate", "up", "down"):     # the experts held elsewhere: 0
        mine[name] = case[name].at[:2].set(0.0).at[6:].set(0.0)
    want = jnp.where(valid[:, None], _plain(mine, 3, form, z), 0.0)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert int(counts["pairs"]) + int(counts["away_pairs"]) == 17 * 3


def _dropless_before(x, router_w, bias, gate_w, up_w, down_w, *, top_k,
                     norm_topk_prob=True, scale=1.0, valid=None,
                     scoring="sigmoid", zero_experts=0, held=None):
    """`dropless_moe_ffn`'s result as the parent commit computed it (its
    body, with what only counts dropped)."""
    T, d = x.shape
    experts = router_w.shape[-1] - zero_experts
    first, E = held or (0, experts)
    top_e, top_w = moe.route_topk(x, router_w, bias, top_k=top_k,
                                  norm_topk_prob=norm_topk_prob,
                                  scale=scale, scoring=scoring)
    identity = None
    if zero_experts or E != experts:
        live = jnp.broadcast_to(True if valid is None else valid[:, None],
                                top_e.shape)
        zero = live & (top_e >= experts)
        here = (top_e >= first) & (top_e < first + E)
        if zero_experts:
            identity = jnp.sum(jnp.where(zero, top_w, 0.0), axis=-1)
        top_e = jnp.where(here, top_e - first, E)
    if valid is not None:
        top_e = jnp.where(valid[:, None], top_e, E)
    flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    load = jnp.bincount(flat_e, length=E + 1)[:E].astype(jnp.int32)
    xs = x[order // top_k]
    h = (jax.nn.silu(gmm.grouped_matmul(xs, gate_w, load))
         * gmm.grouped_matmul(xs, up_w, load))
    ys = gmm.grouped_matmul(h, down_w, load).astype(jnp.float32)
    ys = ys * top_w.reshape(-1)[order][:, None]
    y = ys[jnp.argsort(order)].reshape(T, top_k, d).sum(axis=1)
    if identity is not None:
        y = y + identity[:, None] * x.astype(jnp.float32)
    return y.astype(x.dtype)


@pytest.mark.parametrize("how", [
    # the three classes that route today, as each calls the layer
    dict(top_k=2, scale=1.8),                               # MLAMoE
    dict(top_k=3, scale=1.0, norm_topk_prob=True, valid=True),  # windowed
    dict(top_k=3, scale=6.0, norm_topk_prob=False, scoring="softmax",
         zero_experts=2, held=(2, 3), valid=True)])         # the shortcut's
def test_the_defaults_give_what_the_layer_gave_bit_for_bit(how):
    how = dict(how)
    case = _moe_case(2)
    valid = (jnp.arange(20) < 18) if how.pop("valid", False) else None
    first, E = how.get("held") or (0, 8 - how.get("zero_experts", 0))
    args = (case["x"].astype(jnp.bfloat16), case["router"], case["bias"],
            *(case[n][first:first + E].astype(jnp.bfloat16)
              for n in ("gate", "up", "down")))
    got, _ = moe.dropless_moe_ffn(*args, valid=valid, **how)
    want = _dropless_before(*args, valid=valid, **how)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
