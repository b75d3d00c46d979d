"""The eighth architecture (`models.parallel_hybrid.ParallelHybrid`: a
state-space mixer and grouped-query attention side by side in every layer,
each layer holding a state slot and pages under one index, twelve scalars
on its projections, a SwiGLU behind the mixers) held to its plain reference
(`benchmarks/models/falcon_h1.py`) and to itself: prefill then decode
through the engine's own programs, the kernels under the interpreter, the
fp8 control failing the same check, every scalar alive, a slot reused, an
evicted sequence prefilled again, an inactive lane, and what the engine
counts and writes on its spans. Tiny sizes, CPU, seeded.
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
from ray_tpu.models import (ParallelHybrid,                  # noqa: E402
                            ParallelHybridConfig, build_model, model_config)
from ray_tpu.models.parallel_hybrid import (                 # noqa: E402
    tiny_parallel_hybrid)
from ray_tpu.ops import paged_attention as paged             # noqa: E402
from ray_tpu.ops import ssd                                  # noqa: E402
from ray_tpu.serve.llm import spans as sp                    # noqa: E402
from ray_tpu.serve.llm.engine import EngineCore              # noqa: E402
from test_hybrid_delta import (_greedy, _prefill, _step,     # noqa: E402
                               _through_the_engine)

CONFIG = "falcon-h1-34b-instruct-1chip"
PAGE = 8
TOL = 2e-4
# the twelve published scalars, the two tuples' entries one by one
SCALARS = ["embedding_multiplier", "lm_head_multiplier",
           "attention_in_multiplier", "attention_out_multiplier",
           "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
           *(f"ssm_multipliers[{i}]" for i in range(5)),
           *(f"mlp_multipliers[{i}]" for i in range(2))]


@pytest.fixture(scope="module")
def tiny_ref():
    """(model module, its Sizes at the tiny size, seeded float32 weights,
    the program's config for them): two layers, 10 query heads over 2 kv
    heads (a group of 5), 4 state-space heads of 8 in 2 groups, and the
    tiny preset's multipliers: all twelve unlike 1 and unlike each
    other. Steps of about 0.3 at rates of about 1 (the published
    initialisation's 0.01 and 8.5 leave the scan a hundredth of the skip
    over so few positions, and B's, C's and the step's scalars unseen)."""
    cfg = modelcfg.load_config(CONFIG)
    mod = modelcfg.load_model(cfg)
    preset = tiny_parallel_hybrid()
    small = dict(mod.tiny(cfg), mamba_dt_bias_init=-1.0,
                 mamba_a_log_init=0.0, **{
        name: getattr(preset, name) for name in (
            *mod.MULTIPLIERS, "ssm_multipliers", "mlp_multipliers")})
    sz = mod.sizes(small)
    params = make_weights(mod.weight_shapes(sz), 11, dtype=jnp.float32)
    pc = mod.program_config(small, 256, dtype="float32",
                            param_dtype="float32")
    return mod, sz, params, pc


def _tokens(sz, seed, n, room=256):
    toks = np.zeros((room,), np.int32)
    toks[:n] = np.random.default_rng(seed).integers(0, sz.vocab, n)
    return toks


def test_apply_matches_the_reference_logits(tiny_ref):
    mod, sz, params, pc = tiny_ref
    assert sz.heads // sz.kv_heads == 5
    assert all(m != 1.0 for m in (
        *(getattr(pc, n) for n in mod.MULTIPLIERS), *pc.ssm_multipliers,
        *pc.mlp_multipliers))
    toks = _tokens(sz, 0, 100, room=128)
    got = build_model(pc).apply(params, jnp.asarray(toks[None, :100]))[0]
    want = mod.reference_rows(sz, params, jnp.asarray(toks), jnp.int32(0),
                              100)
    assert rel_rms(got, want) < TOL
    assert build_model(pc).param_count() == mod.param_count(sz)
    loss = build_model(pc).loss(params, {"tokens": jnp.asarray(
        toks[None, :64])})
    want_loss = mod.loss_fn(sz, params, jnp.asarray(toks[:64]))
    assert abs(float(loss) - float(want_loss)) < 1e-4
    grad = jax.grad(build_model(pc).loss)(
        params, {"tokens": jnp.asarray(toks[None, :32])})
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grad))


def _with(pc, scalar: str, value: float):
    """`pc` with one of `SCALARS` replaced."""
    if "[" not in scalar:
        return dataclasses.replace(pc, **{scalar: value})
    name, i = scalar[:-1].split("[")
    values = list(getattr(pc, name))
    values[int(i)] = value
    return dataclasses.replace(pc, **{name: values})


@pytest.mark.parametrize("scalar", SCALARS)
def test_no_scalar_is_dead(tiny_ref, scalar):
    """Each multiplier set to 1 in turn, the reference keeping it: the
    program misses the reference by more than the tolerance it otherwise
    meets."""
    mod, sz, params, pc = tiny_ref
    toks = _tokens(sz, 1, 48, room=128)
    want = mod.reference_rows(sz, params, jnp.asarray(toks), jnp.int32(0),
                              48)
    got = build_model(_with(pc, scalar, 1.0)).apply(
        params, jnp.asarray(toks[None, :48]))[0]
    assert rel_rms(got, want) > 5 * TOL


def test_the_gated_norms_two_orders_differ_and_both_match(tiny_ref):
    mod, sz, params, pc = tiny_ref
    toks = _tokens(sz, 2, 48, room=128)
    rows = {}
    for first in (False, True):
        s2 = dataclasses.replace(sz, norm_before_gate=first)
        rows[first] = mod.reference_rows(s2, params, jnp.asarray(toks),
                                         jnp.int32(0), 48)
        got = build_model(dataclasses.replace(
            pc, mamba_norm_before_gate=first)).apply(
                params, jnp.asarray(toks[None, :48]))[0]
        assert rel_rms(got, rows[first]) < TOL
    assert rel_rms(rows[True], rows[False]) > 0.05


@pytest.mark.parametrize("p,steps", [
    (5, 8),         # shorter than a chunk and a page of 8, a bucket of 16
    (20, 8),        # off a chunk's and a page's edge; the tail's 3 inputs
    (33, 30),       # a bucket of 64, nearly twice the prompt
    (64, 8),        # whole chunks and pages, a bucket that is full
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


def test_the_kernels_under_the_interpreter_give_the_same_logits(
        tiny_ref, monkeypatch):
    """The same check with the scan's two kernels forced on (the Pallas
    interpreter off the TPU). (The paged decode attention tiles no shape
    this small; it is held at this family's grouping below.)"""
    mod, sz, params, pc = tiny_ref
    monkeypatch.setattr(ssd, "ssd_prefill", ssd.ssd_prefill_kernel)
    monkeypatch.setattr(ssd, "ssd_step", ssd.ssd_step_kernel)
    core = EngineCore(pc, params, num_pages=40, page_size=PAGE, max_batch=2)
    p, steps = 21, 8
    toks = _tokens(sz, 3, p + steps)
    got = _through_the_engine(core, toks, p, steps, lane=1)
    want = mod.reference_rows(sz, params, jnp.asarray(toks),
                              jnp.int32(p - 1), steps + 1)
    assert rel_rms(got, want) < TOL


def test_paged_decode_kernel_at_five_query_heads_a_kv_head():
    """20 query heads over 4 kv heads of 128, the published grouping (no
    power of two), through the interpreter against the gathered
    reference."""
    r = np.random.default_rng(0)
    lengths = jnp.asarray([40, 1, 0, 17], jnp.int32)
    B, heads, kv, hd, page, pages = 4, 20, 4, 128, 16, 12
    q = jnp.asarray(r.normal(size=(B, heads, hd)), jnp.float32)
    k_pool, v_pool = (jnp.asarray(r.normal(size=(2, pages, page, kv * hd)),
                                  jnp.float32) for _ in range(2))
    tables = jnp.asarray(r.permutation(pages).reshape(B, 3), jnp.int32)
    got = paged.paged_decode_attention_kernel(q, k_pool, v_pool, 1, tables,
                                              lengths)
    want = paged.paged_attention_reference(q, k_pool, v_pool, 1, tables,
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


def test_eviction_and_re_prefill_give_the_same_greedy_tokens():
    cfg = tiny_parallel_hybrid()
    model = ParallelHybrid(cfg)
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
    # copies: the cache is donated to the next step, its buffers reused
    before = jax.tree.map(np.array, core._cache)
    # lane 0 runs sequence 0; sequence 1 holds its slot and no lane
    _step(core, {0: (toks[20], 17, tables[0])})
    after = jax.tree.map(np.array, core._cache)
    mine, other = pages[0][0], pages[1][0]
    for name in ("state", "tail"):      # in both layers
        assert (after[name][:, other] == before[name][:, other]).all()
        assert (after[name][:, -1] == before[name][:, -1]).all()  # nobody's
        assert all((after[name][li, mine] != before[name][li, mine]).any()
                   for li in range(2))
    for name in ("k", "v"):             # one row, position 17, both layers
        changed = (after[name] != before[name]).any(axis=-1)
        assert changed.sum() == 2 and changed[:, pages[0][2], 1].all()
    # a step of no active lane, and of a lane whose table is unassigned
    # (-1 everywhere), leaves every pool bit for bit as it was
    _step(core, {})
    idle = jax.tree.map(np.array, core._cache)
    _step(core, {2: (toks[3], 5, np.full_like(tables[0], -1))})
    now = jax.tree.map(np.array, core._cache)
    for name in ("k", "v", "state", "tail"):
        assert (idle[name] == after[name]).all(), name
        assert (now[name] == after[name]).all(), name


def test_a_layer_holds_a_slot_and_pages_at_any_length():
    cfg = modelcfg.load_config(CONFIG)
    mod = modelcfg.load_model(cfg)
    served = build_model(mod.program_config(cfg, 2560))
    # 256 x 4096 float32 of state and 3 x 48 x 128 bf16 of tail a layer
    assert served.state_bytes() == 6 * (256 * 4096 * 4 + 3 * 6144 * 2)
    assert served.fixed_step_counts(2000, 16) == served.fixed_step_counts(
        9, 16) == {"state_slots": 1, "state_bytes": 2 * served.state_bytes()}
    assert served.cache_page_bytes(16, fixed=True) == served.state_bytes()
    # six layers of 4 kv heads of 128: 12,288 B a position
    assert served.cache_page_bytes(16) == 6 * 2 * 16 * 512 * 2
    assert served.fixed_pages(16) == 1
    assert served.prefill_counts(1000, 1024) == {"scan_chunks": 8}
    assert served.param_count() == 5254594112 == mod.param_count(
        mod.sizes(cfg)) == cfg["parameters"]
    cache = jax.eval_shape(lambda: served.init_cache(5120, 16,
                                                     fixed_pages=32))
    assert {n: a.shape for n, a in cache.items()} == {
        "k": (6, 5120, 16, 512), "v": (6, 5120, 16, 512),
        "state": (6, 33, 256, 4096), "tail": (6, 33, 3, 48, 128)}
    assert cache["state"].dtype == jnp.float32


def test_the_engine_counts_states_beside_pages_and_writes_its_spans(
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
        and a["state_bytes"] == per_lane * a["lanes"]
        and 0 < a["live_positions"] <= a["read_positions"]
        for a in dispatches)
    prefills = {a["rid"]: a for n, a in seen if n == sp.PREFILL}
    assert prefills["long"]["scan_chunks"] == 4         # 30 tokens, C = 8
    assert prefills["long"]["tokens"] == 30
    assert prefills["long"]["bucket"] == 32
    assert prefills["short"]["scan_chunks"] == 1
    assert core.cache_stats() == {"fixed_pages": 2, "fixed_pages_used": 0}


def test_a_config_names_its_model_and_refusals_are_plain():
    cfg = model_config({
        "type": "parallel_hybrid", "d_model": 64, "n_layers": 2,
        "n_heads": 10, "n_kv_heads": 2, "head_dim": 16, "ssm_heads": 4,
        "ssm_head_dim": 8, "ssm_groups": 2, "ssm_state": 16, "chunk": 8,
        "d_ff": 96, "ssm_multipliers": [1, 2, 3, 4, 5]})
    assert isinstance(cfg, ParallelHybridConfig) and hash(cfg)
    assert isinstance(build_model(cfg), ParallelHybrid)
    assert cfg.ssm_multipliers == (1.0, 2.0, 3.0, 4.0, 5.0)
    assert cfg.ssm_inner == 32 and cfg.conv_channels == 32 + 2 * 32
    assert cfg.kv_dim == 32
    from ray_tpu.parallel.mesh import MeshSpec
    mesh = MeshSpec(dp=1, tp=2).build(jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="no mesh"):
        ParallelHybrid(tiny_parallel_hybrid(), mesh=mesh)
    with pytest.raises(ValueError, match="5 scalars"):
        ParallelHybridConfig(ssm_multipliers=(1.0, 2.0))
    with pytest.raises(ValueError, match="kv heads"):
        ParallelHybridConfig(n_heads=20, n_kv_heads=3)
    # the published widths' step takes the step kernel at half a group
    assert ssd.step_columns(4096, 2048, 256) == 1024
