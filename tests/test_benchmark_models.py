"""Tier-1 guard of the benchmark's seam (PR 28 left it for a PR that may
touch `tests/`): every configuration in BENCHMARK.json names a model module
with the whole interface, its `reduced` keys are accounted for, every cell
finds its files, every metric lists cells that exist, and each module's
program agrees with its own plain reference at `tiny(cfg)` on the CPU.
Also the readers of the per-layer metrics PR 29 added, on events built by
hand.
"""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmarks")

from benchmarks.harness import modelcfg, spans, xplane       # noqa: E402
from benchmarks.harness.peaks import PEAKS                   # noqa: E402
from benchmarks.harness.reference import rel_rms             # noqa: E402
from benchmarks.harness.weights import leaves, make_weights  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CONFIGS = BENCHMARK["configs"]
CELLS = BENCHMARK["workloads"]
METRICS = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]


def _cfg(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def by_name(items):
    return pytest.mark.parametrize("entry", items,
                                   ids=[x["name"] for x in items])


# ------------------------------------------------------ configurations
@by_name(CONFIGS)
def test_configuration_names_a_model_module_with_the_interface(entry):
    cfg = _cfg(entry)
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    model = modelcfg.load_model(cfg)
    for name in modelcfg.INTERFACE:
        assert callable(getattr(model, name)), name
    sz = model.sizes(cfg)
    assert sz.vocab == cfg["vocab_size"]
    flat, _ = leaves(model.weight_shapes(sz))
    assert model.param_count(sz) == sum(
        int(np.prod(shape)) for shape, _ in flat)
    assert cfg.get("parameters", model.param_count(sz)) == \
        model.param_count(sz)
    assert model.matmul_params(sz) < model.param_count(sz)


@by_name(CONFIGS)
def test_reduced_keys_are_listed_with_their_published_values(entry):
    cfg = _cfg(entry)
    assert cfg["reduced"] == entry["reduced"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    for key in cfg["reduced"]:
        assert cfg[key] != cfg["published"][key], key
        # a cut is of depth, context or count (layers, experts held, rows
        # of the vocabulary); never of a width
        assert key == "vocab_size" or not key.endswith(
            ("_dim", "_rank", "_size")), key
    dep = cfg["deployment"]
    assert dep["chips"] in (1, 4)
    if "context_limit" in dep:
        assert dep["context_limit"] <= cfg["max_position_embeddings"]
        assert dep["num_pages"] * dep["page_size"] >= dep["context_limit"]
    ref = cfg["reference"]
    limit = ref["limit"] if "limit" in ref else ref["grad_limit"]
    assert ref["sound_largest"] < limit < ref["control_smallest"]


@by_name(CONFIGS)
def test_program_agrees_with_the_reference_at_tiny_size(entry):
    """The program's full forward in the configuration's own precision
    against `reference_rows` (float32), seeded weights, on the CPU."""
    from ray_tpu.models import build_model
    cfg = _cfg(entry)
    model = modelcfg.load_model(cfg)
    small = model.tiny(cfg)
    sz = model.sizes(small)
    params = make_weights(model.weight_shapes(sz), 3000000021)
    program = build_model(model.program_config(small, max_seq_len=128))
    toks = np.zeros((128,), np.int32)
    toks[:72] = np.random.default_rng(0).integers(0, sz.vocab, 72)
    got = program.apply(params, jnp.asarray(toks[None, :72]))[0, 40:72]
    want = model.reference_rows(sz, params, jnp.asarray(toks),
                                jnp.int32(40), 32)
    assert want.shape == (32, sz.vocab)
    assert rel_rms(got, want) < 0.06
    control = model.reference_rows(sz, params, jnp.asarray(toks),
                                   jnp.int32(40), 32, True)
    assert rel_rms(control, want) > rel_rms(got, want)
    # the train model's loss is the program's, and finite
    loss = model.train_model(small, 72).loss(
        params, {"tokens": jnp.asarray(toks[None, :72])})
    assert abs(float(loss) - float(model.loss_fn(
        sz, params, jnp.asarray(toks[:72])))) < 0.05


# ------------------------------------------------------------- cells
@by_name(CELLS)
def test_cell_finds_its_files_and_reports_enough(entry):
    from benchmarks.harness.cells import load_cell
    _, cell, cfg, mix = load_cell(entry["name"])
    assert cell["chips"] == cfg["deployment"]["chips"] == 1
    assert os.path.isfile(os.path.join(BENCH, "models",
                                       cfg["model"] + ".py"))
    assert os.path.isfile(os.path.join(BENCH, "traffic",
                                       entry["traffic"] + ".json"))
    assert entry["name"] == f"{entry['config']}.{entry['traffic']}"
    assert len(entry["why"]) <= 200
    reports = {g: [m["name"] for m in BENCHMARK[g]
                   if "workloads" not in m or entry["name"] in m["workloads"]]
               for g in ("end_to_end", "per_layer")}
    assert "setup_s" in reports["end_to_end"]
    assert len(reports["end_to_end"]) >= 2 and reports["per_layer"]
    if mix["kind"] == "closed_loop":
        dep = cfg["deployment"]
        assert mix["clients"] >= dep["max_batch"]
        out = mix["output_tokens"]
        longest = mix["prompt_tokens"]["max"] + out.get("exactly",
                                                        out.get("max"))
        assert longest <= dep["context_limit"]


@by_name(METRICS)
def test_metric_has_a_reader_and_lists_cells_that_exist(entry):
    assert os.path.isfile(os.path.join(BENCH, "metrics",
                                       entry["name"] + ".py"))
    cells = {c["name"] for c in CELLS}
    assert set(entry.get("workloads", cells)) <= cells
    if "moves" in entry:
        moved = {m["name"]: m for m in BENCHMARK["end_to_end"]}[
            entry["moves"]]
        assert set(entry["workloads"]) <= set(moved.get("workloads", cells))
        assert callable(metric(entry["name"]))


def test_a_fifth_of_the_cells_may_take_four_chips_and_none_does():
    assert len(CELLS) == 13 and not [c for c in CELLS if c["chips"] != 1]
    assert len({(c["config"], c["traffic"]) for c in CELLS}) == len(CELLS)


# --------------------------------------------- PR 29's metric readers
def metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


GLM = "glm-4.7-flash-1chip"
E = xplane.Event


def _kernel(name, i, start, dur):
    return E(f"%{name}.{i} = bf16[32,2048] custom-call(...), "
             f"custom_call_target=\"tpu_custom_call\"", start, dur)


@pytest.fixture()
def traced_glm_run():
    """Two decode steps of 10 ms from t = 0 and t = 0.02, a prefill between
    them: in a step, every 1.4 ms a latent kernel of 0.2 ms and, in the six
    expert layers, three grouped matmuls of 0.3 ms behind it."""
    cfg = modelcfg.load_config(GLM)
    model = modelcfg.load_model(cfg)
    ops, modules = [], []
    for s, t0 in enumerate((0.0, 0.02)):
        modules.append(E("jit__step(7)", t0, 0.010))
        for layer in range(7):
            t = t0 + 1.4e-3 * layer
            ops.append(_kernel("mla_paged_decode_attn", layer, t, 0.2e-3))
            for j in range(3 if layer else 0):
                ops.append(_kernel("moe_gmm", 3 * layer + j,
                                   t + 0.3e-3 * (j + 1), 0.3e-3))
    modules.append(E("jit__pre(9)", 0.011, 0.008))
    ops += [_kernel("moe_gmm", 90 + j, 0.012 + 1e-3 * j, 0.9e-3)
            for j in range(3)]
    ops.sort(key=lambda e: e.start)
    modules.sort(key=lambda e: e.start)
    steps = []
    for t0 in (0.0, 0.02):
        steps.append(E(spans.DISPATCH, t0, 1e-4, {
            "lanes": 32, "live_positions": 36800, "read_positions": 37000}))
        steps.append(E("engine.emit", t0 + 0.011, 1e-4, {
            "moe_pairs": 768, "moe_experts_touched": 330,
            "moe_load_max": 42}))
    return {"trace": xplane.Trace({0: modules}, {0: ops}, {}, {}),
            "model": model, "sizes": model.sizes(cfg), "cfg": cfg,
            "peaks": PEAKS["TPU v5 lite"], "result": {"traced": {}},
            "_spans": spans.Reading(steps, {}, 0.0)}


def test_mla_decode_roofline_from_live_positions(traced_glm_run):
    run = traced_glm_run
    need = run["model"].mla_decode_call(run["sizes"], 73600, 64)
    # 576 numbers a position a layer, once; queries in, latents out
    assert need["bytes"] == 7 * 2 * (73600 * 576 + 64 * 20 * (576 + 512))
    assert need["flops"] == 2.0 * 20 * (576 + 512) * 73600 * 7
    want = 100 * (need["bytes"] / 819e9) / (14 * 0.2e-3)
    assert metric("kernel.mla_decode_roofline.batch32")(run) == \
        pytest.approx(want, rel=1e-6)
    assert 0 < want < 100


def test_moe_gmm_roofline_counts_touched_experts_and_decode_steps_only(
        traced_glm_run):
    run = traced_glm_run
    need = run["model"].moe_gmm_call(run["sizes"], 1536, 660)
    assert need["bytes"] == 660 * 3 * 2048 * 1536 * 2 + 1536 * 2 * 2048 * 2
    assert need["flops"] == 6.0 * 2048 * 1536 * 1536
    # 36 events inside the two steps; the prefill's three are left out
    want = 100 * (need["bytes"] / 819e9) / (36 * 0.3e-3)
    assert metric("kernel.moe_gmm_roofline.batch32")(run) == \
        pytest.approx(want, rel=1e-6)
    # every expert held would be 64 x 6 x 2 = 768 > 660 touched
    assert need["bytes"] < run["model"].moe_gmm_call(
        run["sizes"], 1536, 768)["bytes"]


def test_step_moe_ms_is_the_span_between_latent_kernels(traced_glm_run):
    # 1.4 ms from a kernel's start to the next, less its own 0.2 ms, six
    # expert layers
    assert metric("step.moe_ms.batch32")(traced_glm_run) == pytest.approx(
        6 * 1.2, rel=1e-6)


def test_expert_counts_read_from_the_emit_spans(traced_glm_run):
    run = traced_glm_run
    assert metric("moe.experts_touched_share.batch32")(run) == \
        pytest.approx(100 * 660 / (64 * 6 * 2))
    assert metric("moe.load_max_over_mean.batch32")(run) == \
        pytest.approx(84 * 64 / 1536)


@pytest.mark.parametrize("name", [
    "kernel.mla_decode_roofline.batch32", "kernel.moe_gmm_roofline.batch32",
    "step.moe_ms.batch32", "moe.experts_touched_share.batch32",
    "moe.load_max_over_mean.batch32"])
def test_new_metrics_leave_the_line_where_there_is_nothing_to_read(
        traced_glm_run, name):
    """The parent's program (no such kernel, no such span attribute), a
    dense model's module, an untraced run: None, and nothing raised."""
    run = dict(traced_glm_run)
    dense = modelcfg.load_config("internlm2-1.8b")
    plain = [E(spans.DISPATCH, 0.0, 1e-4, {"lanes": 8, "live_positions": 9,
                                            "read_positions": 16}),
             E("engine.emit", 0.01, 1e-4, {})]
    run.update(
        trace=xplane.Trace({0: [E("jit__step(7)", 0.0, 0.01)]},
                           {0: [E("%fusion.1 = bf16[8] fusion()", 0, 1e-3)]},
                           {}, {}),
        model=modelcfg.load_model(dense), cfg=dense,
        sizes=modelcfg.load_model(dense).sizes(dense),
        _spans=spans.Reading(plain, {}, 0.0))
    assert metric(name)(run) is None
    run.update(trace=None, _spans=None)
    assert metric(name)(run) is None


# --------------------------------------------- PR 44's metric readers
LONGCAT = "longcat-flash-chat-1chip"
SHARE_METRICS = [
    "step.attn_latent_ms.reason4k", "step.moe_gmm_ms.reason4k",
    "moe.zero_pairs_share.reason4k", "moe.pairs_per_held_expert.reason4k",
    "moe.experts_touched_share.reason4k", "moe.load_max_over_mean.reason4k",
    "kernel.flash_mla_roofline.reason4k"]


@pytest.fixture()
def traced_share_run():
    """Two decode steps of 12 ms from t = 0 and t = 0.03: in a step 8
    latent kernels of 0.15 ms and 12 grouped matmuls of 0.2 ms; between
    them a prefill of 700 tokens with 8 flash forwards of 0.5 ms and three
    grouped matmuls of its own."""
    cfg = modelcfg.load_config(LONGCAT)
    model = modelcfg.load_model(cfg)
    ops, modules = [], []
    for t0 in (0.0, 0.03):
        modules.append(E("jit__step(7)", t0, 0.012))
        for i in range(8):
            ops.append(_kernel("mla_paged_decode_attn", i,
                               t0 + 1.4e-3 * i, 0.15e-3))
        for i in range(12):
            ops.append(_kernel("moe_gmm", i, t0 + 0.9e-3 * i + 0.3e-3,
                               0.2e-3))
    modules.append(E("jit__pre(9)", 0.013, 0.015))
    ops += [_kernel("flash_fwd", i, 0.014 + 1e-3 * i, 0.5e-3)
            for i in range(8)]
    ops += [_kernel("moe_gmm", 90 + j, 0.023 + 1e-3 * j, 0.9e-3)
            for j in range(3)]
    ops.sort(key=lambda e: e.start)
    modules.sort(key=lambda e: e.start)
    host = [E(spans.PREFILL, 0.0125, 1e-4, {"tokens": 700, "bucket": 1024})]
    for t0 in (0.0, 0.03):
        host.append(E(spans.DISPATCH, t0, 1e-4, {
            "lanes": 32, "live_positions": 44800, "read_positions": 45000}))
        host.append(E("engine.emit", t0 + 0.013, 1e-4, {
            "moe_pairs": 30, "moe_experts_touched": 24, "moe_load_max": 9,
            "moe_zero_pairs": 500, "moe_away_pairs": 1006}))
    host.sort(key=lambda e: e.start)
    return {"trace": xplane.Trace({0: modules}, {0: ops}, {}, {}),
            "model": model, "sizes": model.sizes(cfg), "cfg": cfg,
            "peaks": PEAKS["TPU v5 lite"], "result": {"traced": {}},
            "_spans": spans.Reading(host, {}, 0.0)}


def test_share_metrics_read_kernels_and_the_five_counts(traced_share_run):
    run = traced_share_run
    assert metric("step.attn_latent_ms.reason4k")(run) == pytest.approx(
        8 * 0.15)
    # the prefill's grouped matmuls fall in no step
    assert metric("step.moe_gmm_ms.reason4k")(run) == pytest.approx(12 * 0.2)
    # 32 lanes x 12 choices x 4 layers a step, of three kinds
    assert 2 * (30 + 500 + 1006) == 2 * 32 * 12 * 4
    assert metric("moe.zero_pairs_share.reason4k")(run) == pytest.approx(
        100 * 500 / 1536)
    assert metric("moe.pairs_per_held_expert.reason4k")(run) == \
        pytest.approx(60 / (16 * 4 * 2))
    assert metric("moe.experts_touched_share.reason4k")(run) == \
        pytest.approx(100 * 48 / (16 * 4 * 2))
    assert metric("moe.load_max_over_mean.reason4k")(run) == pytest.approx(
        18 * 16 / 60)
    need = run["model"].flash_prefill_call(run["sizes"], 700)
    want = 100 * max(need["flops"] / 197e12,
                     need["bytes"] / 819e9) / (8 * 0.5e-3)
    assert metric("kernel.flash_mla_roofline.reason4k")(run) == \
        pytest.approx(want, rel=1e-6)
    assert 0 < want < 100
    # the accepted readers the cell is listed for read this model's calls
    need = run["model"].mla_decode_call(run["sizes"], 89600, 64)
    assert metric("kernel.mla_decode_roofline.batch32")(run) == \
        pytest.approx(100 * (need["bytes"] / 819e9) / (16 * 0.15e-3),
                      rel=1e-6)
    need = run["model"].moe_gmm_call(run["sizes"], 60, 48)
    assert metric("kernel.moe_gmm_roofline.batch32")(run) == pytest.approx(
        100 * (need["bytes"] / 819e9) / (24 * 0.2e-3), rel=1e-6)


@pytest.mark.parametrize("case", ["reads", "no kernel", "no prefill span",
                                  "no spans", "untraced"])
def test_prefill_flash_ms_is_the_kernels_time_a_traced_prefill(
        traced_share_run, traced_glm_run, case):
    """PR 53's reader: 8 flash forwards of 0.5 ms in one traced prefill;
    None, and nothing raised, for a trace without the kernel (a prefill
    that runs another forward), a program without the span, and an
    untraced run."""
    read = metric("step.prefill_flash_ms")
    run = dict(traced_share_run)
    if case == "reads":
        assert read(run) == pytest.approx(8 * 0.5)
        return
    if case == "no kernel":
        run = dict(traced_glm_run)
    elif case == "no prefill span":
        run["_spans"] = spans.Reading(
            [s for s in run["_spans"].spans if s.name != spans.PREFILL],
            {}, 0.0)
    elif case == "no spans":
        run["_spans"] = None
    else:
        run.update(trace=None, _spans=None)
    assert read(run) is None


@pytest.mark.parametrize("name", SHARE_METRICS)
def test_share_metrics_leave_the_line_where_there_is_nothing_to_read(
        traced_share_run, traced_glm_run, name):
    """The parent's program on this PR's benchmark files (no such span
    attribute), a model that holds every expert, an untraced run: None,
    and nothing raised."""
    run = dict(traced_glm_run)
    if name in ("step.attn_latent_ms.reason4k", "step.moe_gmm_ms.reason4k"):
        assert metric(name)(run) is not None    # GLM's step has the kernels
    else:
        assert metric(name)(run) is None
    run = dict(traced_share_run)
    plain = [E(spans.DISPATCH, 0.0, 1e-4, {"lanes": 8, "live_positions": 9,
                                            "read_positions": 16}),
             E("engine.emit", 0.01, 1e-4, {})]
    run.update(
        trace=xplane.Trace({0: [E("jit__step(7)", 0.0, 0.01)]},
                           {0: [E("%fusion.1 = bf16[8] fusion()", 0, 1e-3)]},
                           {}, {}),
        _spans=spans.Reading(plain, {}, 0.0))
    assert metric(name)(run) is None
    run.update(trace=None, _spans=None)
    assert metric(name)(run) is None


# --------------------------------------------- PR 61's metric readers
GLM5 = "glm-5-1chip"
DSA_METRICS = [
    "step.attn_index_ms.code16k", "step.attn_sparse_ms.code16k",
    "kernel.dsa_index_roofline.code16k", "kernel.dsa_attend_roofline.code16k",
    "kernel.dsa_prefill_roofline.code16k", "dsa.selected_share.code16k"]


@pytest.fixture()
def traced_dsa_run():
    """Two decode steps and a prefill of 5,000 tokens as `op_scopes` files
    them: in a step, five layers of 0.4 ms in `r.attn_index` (a kernel and
    a reduction) and 0.7 ms in `r.attn_core`, 6 ms of everything else; in
    the prefill 30 ms of index scores and 100 ms of masked flash."""
    from benchmarks.harness import op_scopes
    cfg = modelcfg.load_config(GLM5)
    model = modelcfg.load_model(cfg)
    ps = 1e9                                    # picoseconds a millisecond

    def op(path, ms):
        return (op_scopes.OpMeta("%op = f32[1] fusion()", tf_op=path),
                int(ms * ps))

    step_ops = [op("jit(_step)/r.ffn/dot_general", 6.0)]
    for _ in range(5):
        step_ops += [op("jit(_step)/r.attn_index/jit(_paged_index_call)/"
                        "dsa_paged_index/pallas_call", 0.3),
                     op("jit(_step)/r.attn_index/while/body/reduce_sum", 0.1),
                     op("jit(_step)/r.attn_core/jit(_paged_attend_call)/"
                        "dsa_paged_attend/pallas_call", 0.7)]
    pre_ops = [op("jit(_pre)/r.attn_index/dsa_index_scores/pallas_call", 30.0),
               op("jit(_pre)/r.attn_core/dsa_flash_fwd/pallas_call", 100.0),
               op("jit(_pre)/r.moe_experts/moe_gmm/pallas_call", 250.0)]
    dev = op_scopes.DeviceOps([], [
        op_scopes.Execution("jit__step", 7, 0, 0, step_ops),
        op_scopes.Execution("jit__pre", 9, 0, 0, pre_ops),
        op_scopes.Execution("jit__step", 7, 0, 0, list(step_ops))])
    steps = []
    for t0 in (0.0, 0.5):
        steps.append(E(spans.DISPATCH, t0, 1e-4, {
            "lanes": 32, "live_positions": 192000, "read_positions": 524288}))
        steps.append(E("engine.emit", t0 + 0.011, 1e-4, {
            "moe_pairs": 64, "dsa_positions_scored": 5 * 192000,
            "dsa_positions_selected": 5 * 32 * 2048,
            "dsa_lanes_past_topk": 5 * 32}))
    steps.append(E(spans.PREFILL, 0.1, 0.38, {"tokens": 5000,
                                              "bucket": 8192}))
    return {"trace": xplane.Trace({0: []}, {0: []}, {}, {}),
            "model": model, "sizes": model.sizes(cfg), "cfg": cfg,
            "peaks": PEAKS["TPU v5 lite"], "result": {"traced": {}},
            "_spans": spans.Reading(steps, {}, 0.0), "_op_scopes": dev}


def test_dsa_step_regions_and_counts(traced_dsa_run):
    run = traced_dsa_run
    assert metric("step.attn_index_ms.code16k")(run) == pytest.approx(2.0)
    assert metric("step.attn_sparse_ms.code16k")(run) == pytest.approx(3.5)
    assert metric("dsa.selected_share.code16k")(run) == pytest.approx(
        100 * 2048 * 32 / 192000)
    assert metric("cache.index_bytes_share.code16k")(run) == pytest.approx(
        100 * 128 / 768)


def test_dsa_rooflines_count_what_the_algorithm_moves(traced_dsa_run):
    run = traced_dsa_run
    model, sz = run["model"], run["sizes"]
    need = model.dsa_index_call(sz, 384000, 64)
    # a 256-byte key and a float32 score a live position and layer; a
    # lane's 32 index queries of 128 and 32 float32 weights in
    assert need["bytes"] == 5 * (384000 * (256 + 4) + 64 * 32 * (256 + 4))
    assert need["flops"] == 2.0 * 32 * 128 * 384000 * 5
    want = 100 * (need["bytes"] / 819e9) / (2 * 2.0e-3)
    assert metric("kernel.dsa_index_roofline.code16k")(run) == \
        pytest.approx(want, rel=1e-6)
    assert 0 < want < 100
    need = model.dsa_attend_call(sz, 2 * 5 * 32 * 2048, 2 * 32 * 5)
    # 1,152 bytes a chosen row; 64 queries of 576 in and latents of 512 out
    assert need["bytes"] == (2 * 5 * 32 * 2048 * 1152
                             + 2 * 32 * 5 * 64 * (576 + 512) * 2)
    want = 100 * (need["bytes"] / 819e9) / (2 * 3.5e-3)
    assert metric("kernel.dsa_attend_roofline.code16k")(run) == \
        pytest.approx(want, rel=1e-6)
    assert 0 < want < 100
    # reading every live row would be more bytes than the chosen rows: an
    # implementation that does reads low, never above its roofline
    assert need["bytes"] < model.dsa_attend_call(
        sz, 2 * 5 * 192000, 2 * 32 * 5)["bytes"]
    need = model.dsa_prefill_call(sz, 5000)
    chosen = 2048 * 2049 / 2 + (5000 - 2048) * 2048
    assert need["flops"] == 5 * (2.0 * 32 * 128 * 5000 * 5001 / 2
                                 + 2.0 * 64 * 512 * chosen)
    want = 100 * (need["flops"] / 197e12) / 0.130
    assert metric("kernel.dsa_prefill_roofline.code16k")(run) == \
        pytest.approx(want, rel=1e-6)
    assert 0 < want < 100


@pytest.mark.parametrize("name", DSA_METRICS)
def test_dsa_metrics_leave_the_line_where_there_is_nothing_to_read(
        traced_dsa_run, traced_glm_run, name):
    """A program without an indexer (no such region, no such count: every
    other class, and the parent of PR 61), an untraced run: None, and
    nothing raised."""
    from benchmarks.harness import op_scopes
    run = dict(traced_glm_run)
    run["_op_scopes"] = op_scopes.DeviceOps([], [op_scopes.Execution(
        "jit__step", 7, 0, 0, [(op_scopes.OpMeta(
            "%op = f32[1] fusion()",
            tf_op="jit(_step)/r.ffn/dot_general"), 10 ** 9)])])
    assert metric(name)(run) is None
    run = dict(traced_dsa_run)
    run.update(trace=None, _spans=None, _op_scopes=None)
    assert metric(name)(run) is None


# --------------------------------------------- PR 65's metric readers
DOTS3 = "dots3-note-prev-1chip"
RING_METRICS = [
    "kernel.mla_window_decode_roofline.notes12k",
    "step.attn_window_latent_ms.notes12k", "cache.ring_read_share.notes12k",
    "dsa.dense_lanes_share.notes12k"]


@pytest.fixture()
def traced_ring_run():
    """Two decode steps of 10 ms from t = 0 and t = 0.02: in each, three
    ring kernels of 0.1 ms (the sliding layers'); 32 lanes of which 8 are
    under `index_topk`, 30 past the window and 2 of 100 positions."""
    cfg = modelcfg.load_config(DOTS3)
    model = modelcfg.load_model(cfg)
    ops, modules, steps = [], [], []
    seen = 30 * 513 + 2 * 100
    read = 30 * 544 + 2 * 112
    for t0 in (0.0, 0.02):
        modules.append(E("jit__step(7)", t0, 0.010))
        ops += [_kernel("mla_paged_window_decode_attn", j,
                        t0 + 2e-3 * (j + 1), 0.1e-3) for j in range(3)]
        steps.append(E(spans.DISPATCH, t0, 1e-4, {
            "lanes": 32, "live_positions": 160000, "read_positions": 160256}))
        steps.append(E("engine.emit", t0 + 0.011, 1e-4, {
            "moe_pairs": 128, "ring_positions_read": 3 * read,
            "ring_positions_seen": 3 * seen, "dsa_lanes_past_topk": 2 * 24,
            "dsa_positions_scored": 2 * 160000,
            "dsa_positions_selected": 2 * 60000}))
    return {"trace": xplane.Trace({0: modules}, {0: ops}, {}, {}),
            "model": model, "sizes": model.sizes(cfg), "cfg": cfg,
            "peaks": PEAKS["TPU v5 lite"], "result": {"traced": {}},
            "_spans": spans.Reading(steps, {}, 0.0)}, seen, read


def test_ring_metrics_read_the_kernel_and_the_counts(traced_ring_run):
    run, seen, read = traced_ring_run
    model, sz = run["model"], run["sizes"]
    need = model.window_latent_decode_call(sz, 2 * 3 * seen, 2 * 3 * 32)
    # 1,088 numbers a position of the window and sliding layer, once; 64
    # queries of 1,088 in and latents of 1,024 out a lane and layer
    assert need["bytes"] == 2 * (6 * seen * 1088 + 6 * 32 * 64 * (1088
                                                                  + 1024))
    assert need["flops"] == 2.0 * 64 * (1088 + 1024) * 6 * seen
    want = 100 * (need["bytes"] / 819e9) / (6 * 0.1e-3)
    assert metric("kernel.mla_window_decode_roofline.notes12k")(run) == \
        pytest.approx(want, rel=1e-6)
    assert 0 < want < 100
    # whole pages and a row's padding are the program's: never above 100
    assert need["bytes"] < 6 * read * 1152 * 2 + 6 * 32 * 64 * 2112 * 2
    assert metric("step.attn_window_latent_ms.notes12k")(run) == \
        pytest.approx(0.3)
    assert metric("cache.ring_read_share.notes12k")(run) == pytest.approx(
        100 * 3 * read / (3 * read + 2 * 160256))
    assert metric("dsa.dense_lanes_share.notes12k")(run) == pytest.approx(
        100 * 8 / 32)
    # the cell's other readers find their counts beside the ring's
    assert metric("dsa.selected_share.code16k")(run) == pytest.approx(37.5)
    assert metric("cache.index_bytes_share.code16k")(run) == pytest.approx(
        100 * 128 / 768)


@pytest.mark.parametrize("name", RING_METRICS)
def test_ring_metrics_leave_the_line_where_there_is_nothing_to_read(
        traced_ring_run, traced_dsa_run, name):
    """A program without a ring of latents (no such kernel, no such count:
    every other class, and the parent of PR 65), an untraced run: None, and
    nothing raised."""
    assert metric(name)(traced_dsa_run) is None
    run = dict(traced_ring_run[0])
    run.update(trace=None, _spans=None)
    assert metric(name)(run) is None


def test_dots3_required_operations_follow_the_two_geometries():
    cfg = modelcfg.load_config(DOTS3)
    model = modelcfg.load_model(cfg)
    sz = model.sizes(cfg)
    assert (sz.of_kind("full_attention"), sz.of_kind("sliding_attention"),
            sz.of_kind("E")) == ((0, 1), (2, 3, 4), (1, 2, 3, 4))
    assert (sz.full.cache_row, sz.sliding.cache_row) == (576, 1088)
    assert model.param_count(sz) == cfg["parameters"] == 4087154176
    full = model.flash_prefill_call(sz, 5000, "full_attention")
    chosen = 2048 * 2049 / 2 + (5000 - 2048) * 2048
    assert full["flops"] == 2 * 2.0 * 128 * (192 + 128) * chosen
    window = model.flash_window_call(sz, 5000)
    inside = 513 * 514 / 2 + (5000 - 513) * 513
    assert window["flops"] == 3 * 2.0 * 64 * (256 + 128) * inside
    both = model.dsa_prefill_call(sz, 5000)
    assert both["flops"] == (2 * 2.0 * 64 * 128 * 5000 * 5001 / 2
                             + full["flops"] + window["flops"])
    # the reader hands lanes x all five layers: the two full ones' share
    need = model.dsa_attend_call(sz, 1000, 5 * 32)
    assert need["bytes"] == 1000 * 1152 + 2 * 32 * 128 * (576 + 512) * 2
    assert model.dsa_index_call(sz, 1000, 32)["flops"] == \
        2 * 2.0 * 64 * 128 * 1000
