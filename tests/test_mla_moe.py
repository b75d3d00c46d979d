"""The second architecture (`models.mla_moe.MLAMoE`: latent attention, a
dropless routed feed-forward with a shared expert) held to its plain
reference (`benchmarks/models/mla_moe.py`) and to itself: full forward,
prefill then decode through the latent paged cache, absorbed against
expanded attention, the two kernels (through the Pallas interpreter)
against their plain paths, the dropless layer against a per-token loop, the
engine's scheduler on it, loss and gradient. Tiny sizes, CPU, seeded.
"""
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
from benchmarks.harness.reference import rel_rms             # noqa: E402
from benchmarks.harness.weights import make_weights          # noqa: E402
from ray_tpu.models import (MLAMoE, MLAMoEConfig, build_model,  # noqa: E402
                            decode, model_config)
from ray_tpu.models.config import tiny                       # noqa: E402
from ray_tpu.models.mla_moe import tiny_mla_moe              # noqa: E402
from ray_tpu.models.moe import dropless_moe_ffn, route_topk  # noqa: E402
from ray_tpu.ops import grouped_matmul as gmm                # noqa: E402
from ray_tpu.ops import paged_attention as paged             # noqa: E402
from ray_tpu.ops.dispatch import compute_platform            # noqa: E402
from ray_tpu.serve.llm.engine import EngineCore              # noqa: E402
from ray_tpu.serve.llm.kv_cache import pages_from_budget     # noqa: E402

CONFIG = "glm-4.7-flash-1chip"


@pytest.fixture(scope="module")
def tiny_ref():
    """(model module, its tiny Sizes, seeded float32 weights, the
    program's model for them)."""
    cfg = modelcfg.load_config(CONFIG)
    mod = modelcfg.load_model(cfg)
    small = mod.tiny(cfg)
    sz = mod.sizes(small)
    params = make_weights(mod.weight_shapes(sz), 7, dtype=jnp.float32)
    pc = mod.program_config(small, 128, dtype="float32",
                            param_dtype="float32")
    return mod, sz, params, build_model(pc)


def _tokens(vocab, n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, n),
                       jnp.int32)


# ------------------------------------------------------- full forward
def test_apply_matches_the_reference_logits(tiny_ref):
    mod, sz, params, model = tiny_ref
    toks = _tokens(sz.vocab, 48)
    got = model.apply(params, toks[None])[0]
    assert rel_rms(got, mod.logits_fn(sz, params, toks)) < 1e-5


def test_loss_and_gradient_match_the_reference(tiny_ref):
    mod, sz, params, model = tiny_ref
    toks = _tokens(sz.vocab, 32, seed=1)
    loss, grad = jax.value_and_grad(
        lambda p: model.loss(p, {"tokens": toks[None]}))(params)
    want, want_grad = jax.value_and_grad(
        lambda p: mod.loss_fn(sz, p, toks))(params)
    assert abs(float(loss) - float(want)) < 1e-5
    num = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(
        jax.tree.leaves(grad), jax.tree.leaves(want_grad)))
    den = sum(float(jnp.sum(b ** 2)) for b in jax.tree.leaves(want_grad))
    assert (num / den) ** 0.5 < 1e-4
    # every expert matrix and the router got a gradient
    layer = grad["layers"][1]
    assert all(float(jnp.abs(layer[k]).max()) > 0
               for k in ("router", "moe_gate", "moe_down", "shared_up",
                         "wkv_b", "wq_a"))


def test_the_fp8_control_is_told_from_the_reference(tiny_ref):
    mod, sz, params, _ = tiny_ref
    toks = jnp.zeros((128,), jnp.int32).at[:40].set(_tokens(sz.vocab, 40))
    args = (sz, params, toks, jnp.int32(31), 9)
    err = rel_rms(mod.reference_rows(*args, True),
                  mod.reference_rows(*args, False))
    assert err > 0.02


# ------------------------------------------- prefill, decode, the cache
def _prefill_decode(model, params, toks, p, steps, page=8, pages=16,
                    lanes=3, lane=1):
    """Logits of positions p - 1 .. p + steps - 1: one prefill of the first
    p tokens, then `steps` decode steps in one lane of `lanes`, the pages
    handed out in a shuffled order."""
    cache = model.init_cache(pages, page)
    order = np.random.default_rng(3).permutation(pages)
    held = -(-(p + steps) // page)
    pt = np.full((pages,), -1, np.int32)
    pt[:held] = order[:held]
    s_pad = 32
    padded = np.zeros((s_pad,), np.int32)
    padded[:p] = toks[:p]
    pre = jax.jit(lambda *a: model.prefill(*a, page), donate_argnums=(4,))
    step = jax.jit(lambda *a: model.decode_step(*a, page),
                   donate_argnums=(1,))
    logits, cache = pre(params, jnp.asarray(padded), jnp.int32(p),
                        jnp.asarray(pt), cache)
    rows = [logits]
    for k in range(steps):
        tokens = np.zeros((lanes,), np.int32)
        positions = np.zeros((lanes,), np.int32)
        pts = np.full((lanes, pages), -1, np.int32)
        active = np.zeros((lanes,), bool)
        tokens[lane], positions[lane] = toks[p + k], p + k
        pts[lane], active[lane] = pt, True
        logits, cache = step(params, cache, jnp.asarray(tokens),
                             jnp.asarray(positions), jnp.asarray(pts),
                             jnp.asarray(active))
        rows.append(logits[lane])
    return jnp.stack(rows), cache


@pytest.mark.parametrize("p", [5, 16, 23])
def test_prefill_then_decode_matches_apply(tiny_ref, p):
    mod, sz, params, model = tiny_ref
    steps = 6
    toks = _tokens(sz.vocab, p + steps, seed=p)
    got, cache = _prefill_decode(model, params, np.asarray(toks), p, steps)
    want = model.apply(params, toks[None])[0, p - 1:]
    assert rel_rms(got, want) < 1e-5
    # and so the reference's full forward
    assert rel_rms(got, mod.logits_fn(sz, params, toks)[p - 1:]) < 1e-5
    # the decode steps counted their pairs: one lane, k experts a layer
    c = model.config
    assert int(cache["moe_load"].sum()) == (
        steps * c.num_experts_per_tok * c.n_moe_layers)
    assert int(cache["moe_step"]["moe_pairs"]) == (
        c.num_experts_per_tok * c.n_moe_layers)


def test_cache_row_is_the_normed_latent_and_the_rotated_key(tiny_ref):
    _, sz, params, model = tiny_ref
    c = model.config
    toks = np.asarray(_tokens(sz.vocab, 12))
    _, cache = _prefill_decode(model, params, toks, 12, 0, page=4,
                               pages=8)
    assert cache["kv"].shape == (c.n_layers, 8, 4, c.row_width)
    assert c.row_width == 128 and c.kv_lora_rank + c.qk_rope_head_dim == 112
    # the padding past latent + rope is zeros, and three pages are written
    pool = np.asarray(cache["kv"])
    assert not pool[..., 112:].any()
    assert (np.abs(pool[0]).sum(axis=(1, 2)) > 0).sum() == 3


def test_absorbed_attention_equals_expanded(tiny_ref):
    """One layer's attention for the last position: scores against the
    latent rows with W_UK folded into the query, W_UV applied after, equal
    to keys and values expanded a head."""
    _, sz, params, model = tiny_ref
    c = model.config
    layer = params["layers"][1]
    n = 20
    h = jax.random.normal(jax.random.PRNGKey(2), (1, n, c.d_model))
    from ray_tpu.ops.rope import apply_rope_cached, rope_cos_sin
    cos, sin = rope_cos_sin(jnp.arange(n)[None], c.qk_rope_head_dim,
                            c.rope_theta)
    attention = model.attention
    expanded, c_kv, k_rope = attention._attn_expanded(layer, h, cos, sin)
    nope, latent = c.qk_nope_head_dim, c.kv_lora_rank
    q = attention._q(layer, h)[0, -1]                       # (H, qk)
    w = attention._wkv_b(layer)
    q_lat = jnp.einsum("hn,chn->hc", q[:, :nope], w[..., :nope])
    q_rope = apply_rope_cached(q[None, None, :, nope:], cos[:, -1:],
                               sin[:, -1:])[0, 0]
    rows = attention._rows(c_kv[0], k_rope[0], jnp.float32)[None, None]
    q_row = jnp.pad(jnp.concatenate([q_lat, q_rope], -1),
                    ((0, 0), (0, c.row_width - latent
                              - c.qk_rope_head_dim)))
    pool = rows.reshape(1, 1, n, c.row_width)       # one page of n rows
    o_lat = paged.mla_paged_attention_reference(
        q_row[None], pool, 0, jnp.zeros((1, 1), jnp.int32),
        jnp.asarray([n]), latent, c.qk_head_dim ** -0.5)
    out = jnp.einsum("hc,chv->hv", o_lat[0], w[..., nope:]).reshape(-1)
    assert rel_rms(out, expanded[0, -1]) < 1e-5


# ------------------------------------------------- the latent kernel
def _latent_case(lengths, seed=0, heads=5, width=256, latent=128, page=8,
                 pages=24, max_pages=6, layers=2):
    rng = np.random.default_rng(seed)
    B = len(lengths)
    pool = jnp.asarray(rng.normal(size=(layers, pages, page, width)),
                       jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, heads, width)), jnp.float32)
    tables = np.full((B, max_pages), -1, np.int32)
    free = list(rng.permutation(pages))
    for b, n in enumerate(lengths):
        for j in range(-(-n // page)):
            tables[b, j] = free.pop()
    return q, pool, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("lengths", [(1, 9, 48, 17), (0, 8, 0, 33),
                                     (48, 48, 48, 48)])
def test_latent_kernel_matches_gather_and_einsum(lengths):
    """Ragged lengths (an empty lane, a full table), shuffled pages."""
    q, pool, tables, lens = _latent_case(lengths)
    for layer in (0, 1):
        want = paged.mla_paged_attention_reference(
            q, pool, layer, tables, lens, 128, 0.1)
        got = paged.mla_paged_decode_attention_kernel(
            q, pool, layer, tables, lens, 128, 0.1)
        assert got.shape == (4, 5, 128)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_latent_kernel_skips_unassigned_entries():
    q, pool, tables, lens = _latent_case((40, 40))
    holes = np.asarray(tables).copy()
    holes[0, 1] = -1            # a hole inside the live range
    want = paged.mla_paged_attention_reference(
        q, pool, 0, jnp.asarray(holes), lens, 128, 0.1)
    got = paged.mla_paged_decode_attention_kernel(
        q, pool, 0, jnp.asarray(holes), lens, 128, 0.1)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def _latent_blocks_of_64(walk_budget, max_pages):
    """Blocks of 64 8-position pages of 256 float32: four pieces of 128
    positions, multiplied in one or two pieces or whole."""
    page_bytes = 8 * 256 * 4
    walk_budget(paged.BLOCK_SLOTS * 64 * page_bytes)
    assert paged.walk_block_pages(page_bytes, 8, max_pages) == 64
    assert paged.walk_prefixes(64, 8) == (16, 32, 64)


@pytest.mark.parametrize("lengths", [
    (255, 256, 257, 0),         # two pieces' edges (32 pages of 8), idle
    (127, 128, 129, 1),         # a piece's, a position
    (511, 512, 513, 8),         # a block's, a page
    (600, 40, 300, 385),        # unlike lanes: 2 blocks, 1, 1, 1
], ids=lambda v: "-".join(map(str, v)))
def test_latent_kernel_walks_blocks_in_pieces(walk_budget, lengths):
    _latent_blocks_of_64(walk_budget, 80)
    q, pool, tables, lens = _latent_case(lengths, seed=5, pages=330,
                                         max_pages=80)
    holes = np.asarray(tables).copy()
    holes[0, 3] = holes[3, 0] = -1      # in a full block, a lane's first
    if lengths[0] > 500:
        holes[0, 70] = -1               # and in the tail's piece
    holes = jnp.asarray(holes)
    got = paged.mla_paged_decode_attention_kernel(
        q, pool, 1, holes, lens, 128, 0.1)
    np.testing.assert_allclose(got, paged.mla_paged_attention_reference(
        q, pool, 1, holes, lens, 128, 0.1), atol=2e-5, rtol=2e-5)


def test_latent_kernel_reads_pages_in_table_order(walk_budget):
    """The same rows under another placement of the pages give the same
    output (the table, not the pool's order, says where a position is),
    over more than one block of pages a lane."""
    q, pool, tables, lens = _latent_case((150, 60, 129), seed=3, pages=64,
                                         max_pages=40)
    walk_budget(paged.BLOCK_SLOTS * 16 * 8 * 256 * 4)
    assert -(-150 // 8) > paged.walk_block_pages(8 * 256 * 4, 8, 40) == 16
    perm = np.random.default_rng(4).permutation(pool.shape[1])
    inv = np.argsort(perm).astype(np.int32)
    moved = jnp.where(tables >= 0, jnp.asarray(inv)[jnp.maximum(tables, 0)],
                      -1)
    got = paged.mla_paged_decode_attention_kernel(
        q, pool, 1, tables, lens, 128, 0.1)
    again = paged.mla_paged_decode_attention_kernel(
        q, pool[:, perm], 1, moved.astype(jnp.int32), lens, 128, 0.1)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))
    np.testing.assert_allclose(got, paged.mla_paged_attention_reference(
        q, pool, 1, tables, lens, 128, 0.1), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("width,latent,page,dtype,tiles", [
    (640, 512, 16, jnp.bfloat16, True), (576, 512, 16, jnp.bfloat16, False),
    (640, 512, 8, jnp.bfloat16, False), (128, 96, 8, jnp.float32, False),
    (256, 128, 8, jnp.float32, True)])
def test_latent_kernel_path_predicate(width, latent, page, dtype, tiles):
    assert paged.mla_paged_decode_tiles(width, latent, page, dtype) == tiles
    assert not paged.mla_uses_kernel(width, latent, page, dtype)    # CPU
    with compute_platform("tpu"):
        assert paged.mla_uses_kernel(width, latent, page, dtype) == tiles


def test_decode_attention_names_the_latent_kernel_or_einsum():
    served = MLAMoE(MLAMoEConfig(n_layers=2))     # the published widths
    assert served.decode_attention(16) == "einsum"           # off the TPU
    with compute_platform("tpu"):
        assert served.decode_attention(16) == "mla_paged_decode_attn"
        assert MLAMoE(tiny_mla_moe()).decode_attention(8) == "einsum"


# ---------------------------------------- a run of pages a copy (PR 64)
def test_the_latent_walks_run_is_the_pages_bytes_answer():
    """`page_run` at the batch32 cell's shapes: 4 pages a copy, what
    `mla_walk_run_pages` makes of a page of 20,480 B (the least power of
    two that brings `MLA_RUN_COPY_BYTES`), where the kernel runs; 1 on the
    CPU and where the shapes do not tile (the gather reads any table); cut
    to a divisor of the table and of the walk's block."""
    assert paged.mla_walk_run_pages(16 * 640 * 2, 16, 256) == 4
    assert paged.mla_walk_run_pages(16 * 640 * 2, 16, 250) == 2
    assert paged.mla_walk_run_pages(16 * 640 * 2, 16, 255) == 1
    assert paged.mla_walk_run_pages(16 * 128 * 2, 16, 256) == 16
    assert paged.mla_walk_run_pages(16 * 128 * 2, 16, 8) == 8    # the block
    assert paged.mla_walk_run_pages(128 << 10, 16, 256) == 1
    served = MLAMoE(MLAMoEConfig(n_layers=2))     # the published widths
    assert served.page_run(16, 256) == 1                     # off the TPU
    with compute_platform("tpu"):
        assert served.page_run(16, 256) == 4
        assert served.page_run(16, 255) == 1
        assert served.walk_block_pages(16, 256) == 64   # whole runs of 4
        assert MLAMoE(tiny_mla_moe()).page_run(8, 16) == 1       # gathers


# the run each served configuration's class answers its engine at its
# deployment's shapes, where the kernels run: the latent classes by their
# page's bytes, `SparseMLAMoE` and dots3-note-prev's class by their index
# keys' (PR 62), the per-head classes by one pool's page (PR 66: 16 KB asks
# for 4, 8 KB for 8, 32 KB and more for a page a copy). A class that keeps a
# fixed page (a state slot, a ring) has its runs open at table entry `fixed`
# and its table made whole runs behind it (PR 66); a ring's own walk
# answers 1
SERVED_RUNS = {
    "internlm2-1.8b": 1, "laguna-xs.2-1chip": 1, "olmo-hybrid-7b-1chip": 1,
    "nemotron-3-super-120b-a12b-1chip": 8, "ling-3.0-flash-vl-1chip": 4,
    "falcon-h1-34b-instruct-1chip": 4, "lfm2-8b-a1b-1chip": 4,
    "dots3-note-prev-1chip": 8,
    "glm-5-1chip": 8, "glm-4.7-flash-1chip": 4,
    "longcat-flash-chat-1chip": 4}
# (fixed entries, table entries) of the classes whose table PR 66 widened
SERVED_TABLES = {
    "nemotron-3-super-120b-a12b-1chip": (1, 513),
    "ling-3.0-flash-vl-1chip": (1, 1025),
    "falcon-h1-34b-instruct-1chip": (1, 161), "lfm2-8b-a1b-1chip": (1, 257),
    "dots3-note-prev-1chip": (34, 1026)}


@pytest.mark.parametrize("name", sorted(SERVED_RUNS))
def test_a_served_class_answers_its_run_from_shapes_alone(name):
    import json
    from ray_tpu.models.latent import WindowLatentAttention
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", name + ".json")) as f:
        cfg = json.load(f)
    module, dep = modelcfg.load_model(cfg), cfg["deployment"]
    model = build_model(module.program_config(
        cfg, max_seq_len=dep["context_limit"]))
    page, table = dep["page_size"], dep["context_limit"] // dep["page_size"]
    assert model.page_run(page, table) == 1                  # off the TPU
    assert model.table_pages(page, table) == table
    run, fixed = SERVED_RUNS[name], model.fixed_pages(page)
    with compute_platform("tpu"):
        assert model.page_run(page, table) == run
        wide = model.table_pages(page, table)
        assert (fixed, wide) == SERVED_TABLES.get(name, (fixed, table))
        # the step reads the run off the table it is handed: the same
        assert model.page_run(page, wide) == run
        assert model.table_pages(page, wide) == wide
        for mixer in model.mixers:      # a ring begins at any entry
            if isinstance(mixer, WindowLatentAttention) or getattr(
                    mixer, "window", None) is not None:
                assert mixer.page_run(page, table, fixed) == 1
    assert (wide - fixed) % run == 0 and wide >= table
    assert fixed or (table % run == 0 and dep["num_pages"] % run == 0)


def test_the_kernel_is_handed_the_run_the_class_answered(monkeypatch):
    """A step's latent kernel copies the runs `PagedDecoder.page_run` said
    (what the engine's allocator was told), carried in the `Walk`: never
    the mixer's own answer a second time."""
    from ray_tpu.models.latent import LatentAttention
    handed = []
    real = paged.mla_paged_decode_attention

    def spy(*a):
        handed.append(a[7])
        return real(*a[:7])
    monkeypatch.setattr(paged, "mla_paged_decode_attention", spy)
    monkeypatch.setattr(MLAMoE, "page_run", lambda self, *a: 4)
    monkeypatch.setattr(LatentAttention, "page_run", lambda self, *a: 2)
    cfg = tiny_mla_moe()
    model = MLAMoE(cfg)
    B, page = 2, 8
    mp = cfg.max_seq_len // page
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)     # noqa
    jax.eval_shape(
        lambda p, c, t, pos, pts, a: model.decode_step(p, c, t, pos, pts, a,
                                                       page),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)),
        jax.eval_shape(lambda: model.init_cache(B * mp, page)),
        i32(B), i32(B), i32(B, mp), jax.ShapeDtypeStruct((B,), jnp.bool_))
    assert handed == [4] * cfg.n_layers


# ------------------------------------------------- the grouped matmul
@pytest.mark.parametrize("m,sizes,k,n", [
    (128, (0, 5, 0, 100, 3, 0, 0, 10), 128, 256),   # rows past the last
    (1024, (300, 0, 0, 512, 1, 0, 100, 50), 128, 256),  # tiles shared
    (1024, (0, 0, 0, 0, 0, 0, 0, 1024), 128, 256),  # one group has all
    (2048, (300, 0, 0, 200, 1, 0, 100, 50), 128, 256),  # tiles unvisited
    (128, (0,) * 8, 128, 256),                      # nothing to do
    # at the tiles the rule gives since PR 36 (128 rows, the whole n):
    (512, (40, 300, 0, 0, 100, 0, 0, 0), 128, 256),  # a group in 3 tiles
    (128, (10, 20, 5, 30, 1, 2, 40, 16), 128, 256),  # 8 groups, one tile
    (256, (100, 0, 60, 96), 128, 384),      # k < n (down): n one block
    (256, (1, 0, 200, 3), 384, 128),        # k > n (gate and up)
    (48, (7, 0, 30, 2), 128, 128),          # m not a power of two: 16 rows
    (128, (50, 70), 2048, 2048)])           # a matrix of 16 MiB: 2 blocks
def test_grouped_matmul_kernel_matches_ragged_dot(m, sizes, k, n):
    rng = np.random.default_rng(m + sum(sizes))
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), k, n)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    want = lax.ragged_dot(lhs, rhs, gs)
    got = gmm.grouped_matmul_kernel(lhs, rhs, gs)
    np.testing.assert_allclose(got, want, atol=1e-4 * k / 128, rtol=1e-4)
    assert not np.asarray(got[sum(sizes):]).any()


def test_grouped_matmul_work_list_visits_only_groups_with_rows():
    sizes = jnp.asarray((0, 5, 0, 600, 3, 0, 0, 10), jnp.int32)
    tm, _ = gmm.gmm_tile_shape(1024, 128, 256, jnp.float32)
    assert tm == 128
    group, tile, starts, ends, count = gmm.work_list(sizes, 1024, tm)
    n = int(count[0])
    assert n == 8 and group.shape == (8 + 8 - 1,)
    assert group[:n].tolist() == [1, 3, 3, 3, 3, 3, 4, 7]
    assert tile[:n].tolist() == [0, 0, 1, 2, 3, 4, 4, 4]
    # the padding repeats the last real pair: no block moves there
    assert set(group[n:].tolist()) == {7} and set(tile[n:].tolist()) == {4}
    assert (starts[3], ends[3]) == (5, 605)


# (pairs, experts, k, n) of the two expert cells: a decode step's and the
# named prefill bucket's, gate / up and their transpose, down
CELL_SHAPES = [
    (256, 256, 2048, 512), (256, 256, 512, 2048),           # Laguna step
    (128, 64, 2048, 1536), (128, 64, 1536, 2048),           # GLM step
    (8192, 64, 2048, 1536), (8192, 64, 1536, 2048),         # GLM 2048
    (32768, 256, 2048, 512), (32768, 256, 512, 2048)]       # Laguna 4096


@pytest.mark.parametrize("m,groups,k,n", CELL_SHAPES)
def test_grouped_matmul_tiles_at_the_cells_shapes(m, groups, k, n):
    dt = jnp.bfloat16
    tm, tn = gmm.gmm_tile_shape(m, k, n, dt)
    assert m % tm == 0 and tm % 16 == 0         # whole bf16 sublane tiles
    assert tn == n              # a group's matrix is one block, one step
    # an item that fetches a block multiplies no longer than the fetch
    # takes (v5e: 2 tm k n / 197 TFLOP/s against 2 k n bytes / 819 GB/s),
    # so rows thrown away are free
    assert tm <= 197e12 / 819e9
    if m > 256:     # a prefill: the MXU's rows over the rows needed
        assert (m // tm + groups - 1) * tm <= 2.5 * m
    # two of each block in flight fit the limit the call sets, and that
    # is a quarter of a v5e's VMEM at most
    blocks = 2 * 2 * (tm * k + k * tn + tm * tn)
    assert blocks < gmm.gmm_vmem_bytes(tm, tn, k, dt) <= 32 << 20


@pytest.mark.parametrize("m,k,n,dtype,tiles", [
    (100, 2048, 1536, jnp.bfloat16, None),      # no sublane tile in 100
    (1000, 2048, 1536, jnp.bfloat16, None),     # 8 rows: half of one
    (1000, 2048, 1536, jnp.float32, (8, 768)),  # a whole one; 12 MiB
    (128, 2048, 1500, jnp.bfloat16, None),      # columns not whole lanes
    (128, 200, 1536, jnp.bfloat16, None),
    (48, 128, 256, jnp.bfloat16, (16, 256)),
    (4096, 8192, 1536, jnp.bfloat16, (128, 512)),   # 24 MiB: in 3 blocks
    (4096, 8192, 2048, jnp.bfloat16, (128, 512))])  # 32 MiB: in 4
def test_grouped_matmul_tile_rule_and_what_it_refuses(m, k, n, dtype,
                                                      tiles):
    assert gmm.gmm_tile_shape(m, k, n, dtype) == tiles
    assert gmm.gmm_tiles(m, k, n, dtype) == (tiles is not None)
    with compute_platform("tpu"):
        assert gmm.uses_kernel(m, k, n, dtype) == (tiles is not None)


def test_grouped_matmul_path_and_gradient():
    assert not gmm.uses_kernel(128, 2048, 1536, jnp.bfloat16)      # CPU
    with compute_platform("tpu"):
        assert gmm.uses_kernel(128, 2048, 1536, jnp.bfloat16)
        assert gmm.uses_kernel(8192, 1536, 2048, jnp.bfloat16)
        assert not gmm.uses_kernel(100, 2048, 1536, jnp.bfloat16)
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(3, 8, 4)), jnp.float32)
    gs = jnp.asarray((4, 0, 9), jnp.int32)
    g = jax.grad(lambda a, b: gmm.grouped_matmul(a, b, gs).sum(),
                 argnums=(0, 1))(lhs, rhs)
    w = jax.grad(lambda a, b: lax.ragged_dot(a, b, gs).sum(),
                 argnums=(0, 1))(lhs, rhs)
    for a, b in zip(g, w):
        np.testing.assert_allclose(a, b, atol=1e-6)
    assert not np.asarray(g[1][1]).any()        # the group without rows


# ------------------------------------------------- the dropless layer
def _layer_weights(seed=0, d=16, f=8, E=6):
    rng = np.random.default_rng(seed)
    def w(*shape, s=0.3):
        return jnp.asarray(rng.normal(size=shape) * s, jnp.float32)
    return {"router": w(d, E, s=1.0), "bias": jnp.zeros((E,)),
            "gate": w(E, d, f), "up": w(E, d, f), "down": w(E, f, d)}


def _per_token_loop(x, w, top_k, norm, scale, bias=None):
    """The layer one token and one chosen expert at a time, in numpy."""
    bias = np.zeros(w["router"].shape[1]) if bias is None else bias
    out = np.zeros_like(np.asarray(x, np.float64))
    for t, xt in enumerate(np.asarray(x, np.float64)):
        score = 1.0 / (1.0 + np.exp(-(xt @ np.asarray(w["router"],
                                                      np.float64))))
        chosen = np.argsort(-(score + bias), kind="stable")[:top_k]
        weight = score[chosen]
        if norm:
            weight = weight / weight.sum()
        for e, we in zip(chosen, weight * scale):
            g = xt @ np.asarray(w["gate"][e], np.float64)
            u = xt @ np.asarray(w["up"][e], np.float64)
            out[t] += we * ((g / (1 + np.exp(-g)) * u)
                            @ np.asarray(w["down"][e], np.float64))
    return out


@pytest.mark.parametrize("norm,scale", [(True, 1.8), (False, 1.0),
                                        (True, 1.0)])
def test_dropless_layer_matches_a_per_token_loop(norm, scale):
    w = _layer_weights()
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    y, counts = dropless_moe_ffn(
        x, w["router"], w["bias"], w["gate"], w["up"], w["down"], top_k=2,
        norm_topk_prob=norm, scale=scale)
    np.testing.assert_allclose(
        y, _per_token_loop(x, w, 2, norm, scale), atol=2e-5)
    assert int(counts["pairs"]) == 64 and int(counts["load"].sum()) == 64


def test_bias_moves_the_choice_and_not_the_weight():
    w = _layer_weights(seed=1)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 16))
    bias = jnp.asarray([0.0, 0.0, 5.0, 0.0, 0.0, 0.0])  # expert 2 always
    plain_e, plain_w = route_topk(x, w["router"], w["bias"], top_k=2,
                                  norm_topk_prob=False)
    top_e, top_w = route_topk(x, w["router"], bias, top_k=2,
                              norm_topk_prob=False)
    assert (top_e == 2).any(axis=1).all()
    assert not (plain_e == 2).any(axis=1).all()
    # the weight of a chosen expert is its sigmoid score, bias or no bias
    scores = jax.nn.sigmoid(x @ w["router"])
    np.testing.assert_allclose(
        top_w, jnp.take_along_axis(scores, top_e, axis=1), atol=1e-6)
    assert float(top_w.max()) < 1.0
    y, _ = dropless_moe_ffn(x, w["router"], bias, w["gate"], w["up"],
                            w["down"], top_k=2, norm_topk_prob=True,
                            scale=1.8)
    np.testing.assert_allclose(
        y, _per_token_loop(x, w, 2, True, 1.8, np.asarray(bias)),
        atol=2e-5)


def test_no_token_is_dropped_when_one_expert_gets_them_all():
    w = _layer_weights(seed=2)
    x = jax.random.normal(jax.random.PRNGKey(2), (40, 16))
    bias = jnp.asarray([0.0, 9.0, 0.0, 0.0, 0.0, 0.0])
    y, counts = dropless_moe_ffn(x, w["router"], bias, w["gate"], w["up"],
                                 w["down"], top_k=1)
    assert counts["load"].tolist() == [0, 40, 0, 0, 0, 0]
    assert int(counts["touched"]) == 1 and int(counts["pairs"]) == 40
    np.testing.assert_allclose(
        y, _per_token_loop(x, w, 1, True, 1.0, np.asarray(bias)),
        atol=2e-5)
    assert float(jnp.abs(y).min(axis=1).max()) > 0      # every token served


def test_padding_tokens_get_no_pair_and_a_zero_result():
    w = _layer_weights(seed=3)
    x = jax.random.normal(jax.random.PRNGKey(3), (16, 16))
    valid = jnp.arange(16) < 11
    y, counts = dropless_moe_ffn(x, w["router"], w["bias"], w["gate"],
                                 w["up"], w["down"], top_k=2, valid=valid)
    assert int(counts["pairs"]) == 22
    assert not np.asarray(y[11:]).any()
    np.testing.assert_allclose(
        y[:11], _per_token_loop(x[:11], w, 2, True, 1.0), atol=2e-5)


def test_shared_expert_is_counted_once(tiny_ref):
    """The layer's feed-forward is the routed sum plus the shared expert
    applied to every token once, whatever the experts a token chose."""
    _, _, params, model = tiny_ref
    c = model.config
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(4), (10, c.d_model))
    y, counts = model._ffn(layer, x)
    routed, _ = dropless_moe_ffn(
        x, layer["router"], layer["router_bias"], layer["moe_gate"],
        layer["moe_up"], layer["moe_down"], top_k=c.num_experts_per_tok,
        norm_topk_prob=c.norm_topk_prob, scale=c.routed_scaling_factor)
    shared = (jax.nn.silu(x @ layer["shared_gate"])
              * (x @ layer["shared_up"])) @ layer["shared_down"]
    np.testing.assert_allclose(y, routed + shared, atol=1e-5)
    assert int(counts["pairs"]) == 10 * c.num_experts_per_tok


# ------------------------------------------------------- the engine
def _greedy(model, params, prompt, n, pad=32):
    """Greedy tokens of `apply`, one compiled program: the sequence padded
    at its end, which a causal model does not see."""
    apply = jax.jit(model.apply)
    seq = list(prompt)
    for _ in range(n):
        padded = np.zeros((1, pad), np.int32)
        padded[0, :len(seq)] = seq
        seq.append(int(apply(params, jnp.asarray(padded))[0, len(seq) - 1]
                       .argmax()))
    return seq[len(prompt):]


def test_engine_core_serves_greedy_tokens_under_batching_and_eviction():
    cfg = tiny_mla_moe()
    model = MLAMoE(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # a pool too small for all three: the youngest is evicted and resumes
    core = EngineCore(cfg, params, num_pages=5, page_size=8, max_batch=3)
    assert isinstance(core.model, MLAMoE)
    prompts = {"a": [3, 17, 91, 254, 8, 1, 2, 9, 11, 30],
               "b": [5, 6, 7], "c": [200, 100, 50, 25, 12, 6, 3]}
    core.submit(prompts["a"], max_tokens=14, rid="a")
    core.submit(prompts["b"], max_tokens=12, rid="b")
    got = {rid: [] for rid in prompts}
    for i in range(200):
        if i == 2:
            core.submit(prompts["c"], max_tokens=9, rid="c")
        if not core.has_work:
            break
        for ev in core.step():
            got[ev["rid"]].append(ev["token"])
    assert core.counters["evictions"] >= 1
    for rid, n in (("a", 14), ("b", 12), ("c", 9)):
        assert got[rid] == _greedy(model, params, prompts[rid], n), rid
    c = core.counters
    assert c["moe_pairs"] == (c["decode_lane_steps"]
                              * cfg.num_experts_per_tok * cfg.n_moe_layers)
    assert 0 < c["moe_experts_touched"] <= c["moe_pairs"]
    assert c["moe_load_max"] >= c["decode_steps"] * cfg.n_moe_layers
    st = core.device_stats()
    assert st["decode_attention"] == "einsum"
    assert st["cache_bytes_per_position"] == cfg.n_layers * 128 * 4
    assert np.asarray(st["moe_load"]).shape == (2, 8)
    assert np.asarray(st["moe_load"]).sum() == c["moe_pairs"]


def test_the_dense_engine_is_asked_the_same_questions():
    cfg = tiny()
    core = EngineCore(cfg, build_model(cfg).init(jax.random.PRNGKey(0)),
                      num_pages=8, page_size=8, max_batch=2)
    st = core.device_stats()
    assert st["decode_attention"] == "einsum"
    assert st["cache_bytes_per_position"] == decode.cache_page_bytes(
        cfg, 8) // 8
    assert "moe_load" not in st and core.model.step_stats(core._cache) == {}
    core.submit([1, 2, 3], max_tokens=3, rid="d")
    while core.has_work:
        core.step()
    assert "moe_pairs" not in core.counters     # the engine names none


def test_the_engine_sums_whatever_the_model_counts(monkeypatch):
    """The engine's counters take the model's names: one it has never
    heard of is counted like the experts' own."""
    monkeypatch.setattr(MLAMoE, "step_stats", lambda self, cache: {
        "lanes_given_an_expert": cache["moe_step"]["moe_pairs"]})
    cfg = tiny_mla_moe()
    core = EngineCore(cfg, build_model(cfg).init(jax.random.PRNGKey(0)),
                      num_pages=8, page_size=8, max_batch=2)
    assert core.counters["lanes_given_an_expert"] == 0
    core.submit([1, 2, 3], max_tokens=3, rid="d")
    while core.has_work:
        core.step()
    assert core.counters["lanes_given_an_expert"] == (
        core.counters["decode_lane_steps"] * cfg.num_experts_per_tok
        * cfg.n_moe_layers)
    assert "moe_pairs" not in core.counters


def test_pages_from_budget_goes_through_the_model():
    dense, latent = tiny(), tiny_mla_moe()
    assert pages_from_budget(dense, 8, 1 << 20) == (1 << 20) // (
        decode.cache_page_bytes(dense, 8))
    per_page = latent.n_layers * 8 * latent.row_width * 4
    assert MLAMoE(latent).cache_page_bytes(8) == per_page
    assert pages_from_budget(latent, 8, 1 << 20) == (1 << 20) // per_page
    # the latent is shared by every head: a tp shard holds it whole
    assert pages_from_budget(latent, 8, 1 << 20, tp_shards=2) == (
        (1 << 20) // per_page)
    assert pages_from_budget(dense, 8, 1 << 20, tp_shards=2) == 2 * (
        pages_from_budget(dense, 8, 1 << 20))
    # at the published widths: 576 numbers padded to 640, 7 layers, bf16
    served = MLAMoEConfig(n_layers=7)
    assert MLAMoE(served).cache_page_bytes(16) == 16 * 640 * 2 * 7


def test_a_config_names_its_model_and_refusals_are_plain():
    assert isinstance(build_model(tiny_mla_moe()), MLAMoE)
    assert isinstance(model_config({"type": "mla_moe", "d_model": 64,
                                    "qk_nope_head_dim": 16,
                                    "qk_rope_head_dim": 16,
                                    "v_head_dim": 32}), MLAMoEConfig)
    assert model_config("tiny") == tiny()
    from ray_tpu.parallel.mesh import MeshSpec
    mesh = MeshSpec(dp=1, tp=2).build(jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="no mesh"):
        MLAMoE(tiny_mla_moe(), mesh=mesh)
    with pytest.raises(ValueError, match="sigmoid"):
        MLAMoEConfig(scoring_func="softmax")
    import dataclasses
    routed = dataclasses.replace(tiny(), moe_num_experts=4)
    with pytest.raises(NotImplementedError, match="dropless"):
        decode.init_paged_cache(routed, 8, 8)
