"""The class of two latent geometries (`models.SparseWindowMLAMoE`: sparse
latent attention under an indexer on its full layers, latent attention in a
ring on its sliding ones) against `benchmarks/models/dots3_note.py`'s plain
reference and against itself: the ring's decode kernel under the Pallas
interpreter at lengths under, at and past the window and across the ring's
wrap (a window that is no multiple of the page), a lane under the window
against the unwindowed latent decode, prefill then decode across the window
and `index_topk`, held experts that do not start at 0, the shares of an
expert layer adding up, the engine's counters.
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
from ray_tpu.models import (MODELS, SparseWindowMLAMoE,      # noqa: E402
                            SparseWindowMLAMoEConfig, build_model,
                            model_config)
from ray_tpu.models.latent import (RING_COUNTS,              # noqa: E402
                                   WindowLatentAttention)
from ray_tpu.models.moe import dropless_moe_ffn              # noqa: E402
from ray_tpu.models.sparse_mla_moe import DSA_COUNTS         # noqa: E402
from ray_tpu.models.sparse_window_mla_moe import (           # noqa: E402
    FULL, SLIDING, tiny_sparse_window_mla_moe)
from ray_tpu.ops import paged_attention as paged             # noqa: E402
from ray_tpu.serve.llm.engine import EngineCore, _bucket     # noqa: E402

CONFIG = "dots3-note-prev-1chip"
PAGE, TOPK, WINDOW, CONTEXT, LANES = 16, 32, 37, 256, 4


# ------------------------------------------------- the ring's decode entry
def _ring_case(lengths, window=513, heads=8, width=256, latent=128,
               seed=0):
    """Lanes of `lengths` positions whose rows lie in rings of
    `ring_pages(window)` pages of 16 (logical page j at table entry j mod
    ring, what fell out overwritten, entries no position reached
    unassigned): (q, pool, tables, lengths, rows) with `rows[b]` lane b's
    whole history, position by position."""
    rng = np.random.default_rng(seed)
    ring = paged.ring_pages(window, PAGE)
    B = len(lengths)
    pool = np.zeros((2, B * ring + 1, PAGE, width), np.float32)
    tables = np.full((B, ring), -1, np.int32)
    rows = []
    for b, n in enumerate(lengths):
        hist = rng.standard_normal((n, width)).astype(np.float32)
        hist[:, latent + 64:] = 0.0             # a row's padding
        ids = b * ring + rng.permutation(ring)
        held = min(-(-n // PAGE), ring)
        for pos in range(n):
            entry = (pos // PAGE) % ring
            tables[b, entry] = ids[entry]
            pool[1, ids[entry], pos % PAGE] = hist[pos]
        assert (tables[b] >= 0).sum() == held
        rows.append(hist)
    q = rng.standard_normal((B, heads, width)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
            jnp.asarray(np.array(lengths, np.int32)), rows)


def _plain(q, rows, window, latent, sm_scale):
    """The softmax over a lane's last `window` rows, straightforwardly."""
    out = []
    for qb, hist in zip(np.asarray(q), rows):
        seen = hist[-window:]
        if not len(seen):
            out.append(np.zeros((qb.shape[0], latent), np.float32))
            continue
        s = qb @ seen.T * sm_scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out.append((p / p.sum(-1, keepdims=True)) @ seen[:, :latent])
    return np.stack(out)


# under, at and one past the window; across the ring's wrap (34 pages of
# 16: 544 positions) once and twice; an empty lane
LENGTHS = [0, 5, 512, 513, 514, 544, 545, 600, 1100]


def test_the_ring_reference_is_the_softmax_over_the_last_window_rows():
    q, pool, tables, lengths, rows = _ring_case(LENGTHS)
    got = paged.mla_paged_window_attention_reference(
        q, pool, 1, tables, lengths, 128, 0.07, 513)
    assert paged.ring_pages(513, PAGE) == 34 and 513 % PAGE
    np.testing.assert_allclose(got, _plain(q, rows, 513, 128, 0.07),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [513, 37])
def test_the_ring_kernel_under_the_interpreter(window):
    lengths = LENGTHS if window == 513 else [0, 3, 36, 37, 38, 64, 65, 150]
    q, pool, tables, lengths, rows = _ring_case(lengths, window)
    got = paged.mla_paged_window_decode_attention_kernel(
        q, pool, 1, tables, lengths, 128, 0.07, window)
    want = paged.mla_paged_window_attention_reference(
        q, pool, 1, tables, lengths, 128, 0.07, window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, _plain(q, rows, window, 128, 0.07),
                               rtol=2e-5, atol=2e-5)


def test_a_lane_under_the_window_reads_what_the_unwindowed_decode_reads():
    q, pool, tables, lengths, _ = _ring_case([1, 17, 300, 512, 513])
    for entry in (paged.mla_paged_window_attention_reference,
                  paged.mla_paged_window_decode_attention_kernel):
        got = entry(q, pool, 1, tables, lengths, 128, 0.07, 513)
        want = paged.mla_paged_attention_reference(
            q, pool, 1, tables, lengths, 128, 0.07)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        assert got.shape == (5, 8, 128)


def test_the_ring_entry_refuses_what_it_cannot_tile_or_hold():
    q, pool, tables, lengths, _ = _ring_case([40])
    with pytest.raises(ValueError, match="ring of 34"):
        paged.mla_paged_window_decode_attention_kernel(
            q, pool, 1, tables[:, :20], lengths, 128, 0.07, 513)
    with pytest.raises(ValueError, match="does not tile"):
        paged.mla_paged_window_decode_attention_kernel(
            q, pool, 1, tables, lengths, 96, 0.07, 513)


# ------------------------------------------- against the plain reference
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


def _serve(model, params, toks, p, steps, lane=2):
    """Logits of prefill (bucketed, padded) then `steps` decode steps of
    one sequence in lane `lane`, its first table entries pages of the fixed
    class; returns (rows, cache)."""
    fixed = model.fixed_pages(PAGE)
    cache = model.init_cache(64, PAGE, fixed_pages=fixed * LANES)
    table = np.full((CONTEXT // PAGE,), -1, np.int32)
    n = -(-(p + steps) // PAGE)
    table[:n] = [lane * fixed + j if j < fixed else fixed * LANES + 5 + j
                 for j in range(n)]
    s_pad = _bucket(p, hi=CONTEXT)
    padded = np.zeros((s_pad,), np.int32)
    padded[:p] = toks[:p]
    logits, cache = jax.jit(model.prefill, static_argnums=(5,))(
        params, jnp.asarray(padded), jnp.int32(p), jnp.asarray(table),
        cache, PAGE)
    rows = [logits]
    step = jax.jit(model.decode_step, static_argnums=(6,))
    for k in range(steps):
        tokens = np.zeros((LANES,), np.int32)
        positions = np.zeros((LANES,), np.int32)
        tables = np.full((LANES, CONTEXT // PAGE), -1, np.int32)
        active = np.zeros((LANES,), bool)
        tokens[lane], positions[lane] = toks[p + k], p + k
        tables[lane], active[lane] = table, True
        logits, cache = step(params, cache, jnp.asarray(tokens),
                             jnp.asarray(positions), jnp.asarray(tables),
                             jnp.asarray(active), PAGE)
        rows.append(logits[lane])
    return jnp.stack(rows), cache


@pytest.mark.parametrize("p,steps", [
    (20, 8),        # under the window and index_topk throughout
    (30, 12),       # crosses index_topk, then the window, while decoding
    (37, 6),        # a prompt of exactly the window
    (50, 30),       # the ring of 4 pages wraps while decoding
    (100, 12),      # a bucket of 128: the prefill writes the ring's last 4
])
def test_prefill_then_decode_match_the_reference(tiny_ref, p, steps):
    mod, sz, params, model = tiny_ref
    assert (sz.window, model.fixed_pages(PAGE)) == (WINDOW, 4)
    toks = np.random.default_rng(p).integers(0, sz.vocab, p + steps).astype(
        np.int32)
    got, cache = _serve(model, params, toks, p, steps)
    full = np.zeros((CONTEXT,), np.int32)
    full[:p + steps] = toks
    args = (sz, params, jnp.asarray(full), jnp.int32(p - 1), steps + 1)
    assert rel_rms(got, mod.reference_rows(*args)) < 1e-5
    # the selection and the window are what the logits stand on: for the
    # last query, past either, the reference that ignores it is another
    # function
    held = p + steps
    for ignored, limit in (({"dense": True}, TOPK), ({"windowless": True},
                                                     WINDOW)):
        other = rel_rms(got[-1], mod.reference_rows(*args, **ignored)[-1])
        assert (other > 1e-3) if held > limit else (other < 1e-5)
    # the last step's counts
    stats = {k: int(v) for k, v in model.step_stats(cache).items()}
    n_full, n_ring = len(sz.of_kind(FULL)), len(sz.of_kind(SLIDING))
    assert stats["dsa_positions_selected"] == n_full * min(held, TOPK)
    assert stats["dsa_lanes_past_topk"] == n_full * (held > TOPK)
    assert stats["ring_positions_seen"] == n_ring * min(held, WINDOW)
    first = max(held - WINDOW, 0) // PAGE
    assert stats["ring_positions_read"] == n_ring * PAGE * (
        -(-held // PAGE) - first)


def test_a_prompt_under_min_prefill_runs_the_program_of_min_prefill(
        tiny_ref):
    """The deployment's floor (`deployment.min_prefill`, 64 at rehearsal
    size): a bucket of 32 gives the logits and the pools it gives without
    the floor, through the operations of the 64 bucket."""
    mod, sz, params, model = tiny_ref
    assert model.config.min_prefill == 64
    plain = build_model(dataclasses.replace(model.config, min_prefill=0))
    toks = np.random.default_rng(5).integers(0, sz.vocab, 40).astype(
        np.int32)
    (got, cache), (want, want_cache) = (
        _serve(m, params, toks, 20, 10) for m in (model, plain))
    assert rel_rms(got, want) < 1e-6
    for name in ("kv", "idx", "kv_w"):
        np.testing.assert_allclose(cache[name], want_cache[name], atol=1e-6)

    def shapes(m, s):
        text = str(jax.make_jaxpr(m.prefill, static_argnums=(5,))(
            params, jnp.zeros((s,), jnp.int32), jnp.int32(s - 3),
            jnp.zeros((CONTEXT // PAGE,), jnp.int32),
            m.init_cache(64, PAGE, fixed_pages=4 * LANES), PAGE))
        return f"f32[1,64,{sz.d_model}]" in text, (
            f"f32[1,32,{sz.d_model}]" in text)
    assert shapes(model, 32) == (True, False) == shapes(plain, 64)
    assert shapes(plain, 32) == (False, True)


def test_apply_is_the_reference_past_the_window_and_index_topk(tiny_ref):
    mod, sz, params, model = tiny_ref
    toks = np.random.default_rng(1).integers(0, sz.vocab, 128).astype(
        np.int32)
    got = jax.jit(model.apply)(params, jnp.asarray(toks[None]))[0]
    assert rel_rms(got, mod.logits_fn(sz, params, jnp.asarray(toks))) < 1e-5


def test_held_experts_that_do_not_start_at_zero_and_scales_other_than_one():
    mod, sz, params, model = _ref(deployment={"experts_held": [4, 8]})
    assert (sz.first_held, sz.held, model.config.held) == (4, 4, (4, 4))
    assert sz.full.a_kv != sz.sliding.a_kv and sz.full.a_q > 1.0
    g = model.config.geometry(SLIDING)
    assert (g.q_lora_scale, g.kv_lora_scale, g.head_gate) == (
        sz.sliding.a_q, sz.sliding.a_kv, True)
    toks = np.random.default_rng(3).integers(0, sz.vocab, 90).astype(
        np.int32)
    got, _ = _serve(model, params, toks, 60, 30)
    full = np.zeros((CONTEXT,), np.int32)
    full[:90] = toks
    want = mod.reference_rows(sz, params, jnp.asarray(full), jnp.int32(59),
                              31)
    assert rel_rms(got, want) < 1e-5
    # the scales and the gate are what the logits stand on
    for off in ({"apply_mla_qkv_lora_rescale": False},
                {"attention_gate_type": None,
                 "swa_attention_gate_type": None}):
        cfg = modelcfg.load_config(CONFIG)
        other = mod.sizes({**mod.tiny(cfg), "index_topk": TOPK,
                           "deployment": {"experts_held": [4, 8]}, **off})
        plain = {**params, "layers": [
            {k: v for k, v in layer.items() if k != "w_head_gate"
             or other.gate} for layer in params["layers"]]}
        assert rel_rms(got, mod.reference_rows(
            other, plain, jnp.asarray(full), jnp.int32(59), 31)) > 0.01


def test_the_tiny_preset_serves_what_it_applies():
    """The program's own preset (window 21, held experts 4-7) through
    prefill and decode against its own whole-sequence forward."""
    cfg = tiny_sparse_window_mla_moe()
    model = SparseWindowMLAMoE(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(0, 256, 100).astype(np.int32)
    got, _ = _serve(model, params, toks, 40, 60)
    want = model.apply(params, jnp.asarray(toks[None]))[0, 39:]
    assert rel_rms(got, want) < 1e-5


# ----------------------------------------------------- one chip's share
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(tiny_ref):
    """Two chips share a layer of 8 experts, 4 each (as 8 share
    dots3-note-prev's 256): the held experts' parts of both, with what
    every chip computes alike (the attention under its gate, the shared
    expert) counted once, are the uncut layer, in the reference and in the
    program."""
    mod, sz, _, _ = tiny_ref
    whole = dataclasses.replace(sz, first_held=0, held=sz.experts)
    layer = make_weights(mod.weight_shapes(whole)["layers"][2], 13,
                         dtype=jnp.float32)
    assert "w_head_gate" in layer and sz.kinds[2] == SLIDING
    x = jax.random.normal(jax.random.PRNGKey(4), (40, sz.d_model))
    positions = jnp.arange(40)
    uncut = mod._block(whole, SLIDING, x, layer, positions, _ident)
    # what every chip computes alike: the stream after the attention, and
    # the shared expert on its normed form
    from benchmarks.harness.reference import _rms
    after = x + mod._attention(
        sz, SLIDING, _rms(x, layer["attn_norm"], sz.norm_eps), layer,
        positions, _ident, False)
    u = _rms(after, layer["mlp_norm"], sz.norm_eps)
    shared = mod.shared_part(u, layer, _ident)
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
    assert rel_rms(after + sum(ref_parts) + shared, uncut) < 1e-5
    assert rel_rms(after + sum(prog_parts) + shared, uncut) < 1e-5
    assert pairs == 40 * sz.top_k       # every pair is one share's
    # (the feed-forward alone, which the stream is a thousand times)
    ffn = uncut - after
    assert rel_rms(sum(prog_parts) + shared, ffn) < 1e-3
    assert rel_rms(ref_parts[0] + shared, ffn) > 0.1


# ------------------------------------------------------------ the engine
def _greedy(model, params, prompt, n, pad=40):
    """`n` greedy tokens by the whole-sequence forward, the sequence
    padded to one length (every layer is causal): one program."""
    toks = list(prompt)
    apply = jax.jit(model.apply)
    for _ in range(n):
        padded = np.zeros((1, pad), np.int32)
        padded[0, :len(toks)] = toks
        toks.append(int(jnp.argmax(apply(params, jnp.asarray(padded))[
            0, len(toks) - 1])))
    return toks[len(prompt):]


def test_engine_core_serves_it_and_counts_its_rings():
    cfg = tiny_sparse_window_mla_moe(index_topk=16, sliding_window=13)
    model = SparseWindowMLAMoE(cfg)
    params = model.init(jax.random.PRNGKey(0))
    core = EngineCore(cfg, params, num_pages=24, page_size=8, max_batch=3)
    assert isinstance(core.model, SparseWindowMLAMoE)
    assert core.alloc.fixed_pages == 3 * paged.ring_pages(13, 8) == 9
    assert core.alloc.run == 1      # the gathers read any table: no runs
    assert core.max_pages_per_seq == cfg.max_seq_len // 8
    rng = np.random.default_rng(0)
    prompts = {"a": rng.integers(0, 256, 21).tolist(),  # past both limits
               "b": [5, 6, 7],                          # under both
               "c": rng.integers(0, 256, 12).tolist()}  # crosses both
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
    assert set(DSA_COUNTS) | set(RING_COUNTS) <= set(c)
    n_full = cfg.layer_types.count(FULL)
    assert c["dsa_positions_scored"] == c["kv_positions_live"] * n_full
    assert 0 < c["ring_positions_seen"] <= c["ring_positions_read"]
    assert c["ring_positions_seen"] < c["kv_positions_live"] * (
        cfg.n_layers - n_full)
    st = core.device_stats()
    assert st["decode_attention"] == "einsum"
    # full layers: a latent row of 128 numbers and an index key of 32
    assert st["cache_bytes_per_position"] == n_full * (128 + 32) * 4
    assert model.cache_page_bytes(8, fixed=True) == 3 * 8 * 256 * 4


def test_a_config_names_the_class_and_its_two_geometries():
    assert MODELS["sparse_window_mla_moe"] == (SparseWindowMLAMoEConfig,
                                               SparseWindowMLAMoE)
    cfg = model_config({"type": "sparse_window_mla_moe", "n_layers": 5,
                        "experts_held": (0, 32)})
    model = build_model(cfg)
    assert isinstance(model, SparseWindowMLAMoE)
    assert cfg.layer_types == (FULL, FULL, SLIDING, SLIDING, SLIDING)
    assert SparseWindowMLAMoEConfig().layer_types.count(FULL) == 13
    full, ring = model.attention, model.window_attention
    assert isinstance(ring, WindowLatentAttention)
    assert (full.config.n_heads, full.config.row_width) == (128, 640)
    assert (ring.config.n_heads, ring.config.row_width, ring.window) == (
        64, 1152, 513)
    assert model.fixed_pages(16) == 34 and model.page_run(16, 1024) == 1
    assert model.table_pages(16, 1024) == 1024
    # where the kernels run the two sparse walks ask for runs of 8 behind
    # the ring's 34 entries (PR 66), the ring's own walk for none
    from ray_tpu.ops.dispatch import compute_platform
    with compute_platform("tpu"):
        assert model.page_run(16, 1024) == model.page_run(16, 1026) == 8
        assert model.table_pages(16, 1024) == 34 + 992
        assert full.page_run(16, 1024, 34) == 8
        assert ring.page_run(16, 1024, 34) == 1
    assert model.cache_page_bytes(16) == 2 * 16 * (640 + 128) * 2
    assert model.cache_page_bytes(16, fixed=True) == 3 * 16 * 1152 * 2
    assert model.index_page_bytes(16) == 2 * 16 * 128 * 2
    assert [(pool.name, layers) for pool, layers in model.pools] == [
        ("kv", 2), ("idx", 2), ("kv_w", 3)]
    assert model.param_count() == 870723840 * 3 + 923938816 \
        + 356396800 + 2 * 152064 * 5120 + 5120
    with pytest.raises(ValueError, match="layer_types"):
        SparseWindowMLAMoEConfig(n_layers=5, layer_types=(FULL, SLIDING))
    with pytest.raises(NotImplementedError, match="its ring"):
        SparseWindowMLAMoE(tiny_sparse_window_mla_moe(), mesh=object())
