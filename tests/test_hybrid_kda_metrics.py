"""The readers of the per-layer metrics PR 50 added for the cell
`ling-3.0-flash-vl-1chip.serve.docs16k`, on a trace built by hand: each
reads what its docstring says, and leaves the line (None, nothing raised)
where the program writes no such span or kernel: the parent's program,
another model's module, an untraced run. Also the accepted readers that
the cell is listed under, on the same trace, and that BENCHMARK.json lists
the cell where its readers find something and nowhere else."""
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmarks")

from benchmarks.harness import modelcfg, spans, xplane       # noqa: E402
from benchmarks.harness.peaks import PEAKS                   # noqa: E402

E = xplane.Event
LING = "ling-3.0-flash-vl-1chip"
CELL = LING + ".serve.docs16k"
NEW = ["step.attn_kda_ms.docs16k", "kernel.kda_step_roofline.docs16k",
       "kernel.kda_chunk_roofline.docs16k", "cache.state_bytes_share.docs16k"]
# accepted readers that return a number for this model
SHARED = ["step.decode_ms.batch", "step.prefill_ms.answers3k",
          "kernel.mla_decode_roofline.batch32",
          "kernel.flash_mla_roofline.reason4k",
          "step.attn_latent_ms.reason4k", "kernel.moe_gmm_roofline.batch32",
          "step.moe_gmm_ms.reason4k", "moe.pairs_per_held_expert.agent8k",
          "moe.experts_touched_share.agent8k",
          "moe.load_max_over_mean.reason4k"]
STATE = 128 * 4096 * 4          # a layer's state of one sequence, bytes
TAIL = 3 * 12288 * 2


def metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _kernel(name, i, start, dur):
    return E(f"%{name}.{i} = f32[32,1,4096] custom-call(...), "
             f"custom_call_target=\"tpu_custom_call\"", start, dur)


@pytest.fixture()
def traced_run():
    """Two decode steps of 9 ms from t = 0 and t = 0.1 with a prefill of
    80 ms between them. In a step a layer begins every 1.2 ms with its
    mixer's kernel (0.25 ms the recurrence, 0.3 ms the latent attention)
    and an expert layer's three grouped matmuls of 0.2 ms follow; in the
    prefill each KDA layer's chunk kernel takes 4 ms and the latent
    layer's flash forward 6."""
    cfg = modelcfg.load_config(LING)
    model = modelcfg.load_model(cfg)
    sz = model.sizes(cfg)
    ops, modules, host = [], [], []
    for t0 in (0.0, 0.1):
        modules.append(E("jit__step(7)", t0, 0.009))
        for layer, kind in enumerate(sz.layer_types):
            latent = kind == "latent_attention"
            t = t0 + 1.2e-3 * layer
            ops.append(_kernel("mla_paged_decode_attn" if latent
                               else "kda_step", layer, t,
                               0.3e-3 if latent else 0.25e-3))
            for j in range(3 if layer else 0):
                ops.append(_kernel("moe_gmm", 3 * layer + j,
                                   t + 0.35e-3 + 0.2e-3 * j, 0.2e-3))
        host.append(E(spans.DISPATCH, t0, 1e-4, {
            "lanes": 32, "live_positions": 90000, "read_positions": 90256,
            "state_slots": 32, "state_bytes": 32 * 2 * 6 * (STATE + TAIL)}))
        host.append(E("engine.emit", t0 + 0.01, 1e-4, {
            "moe_pairs": 380, "moe_experts_touched": 210,
            "moe_load_max": 40, "moe_zero_pairs": 0,
            "moe_away_pairs": 1156}))
    modules.append(E("jit__pre(9)", 0.02, 0.080))
    ops += [_kernel("kda_chunk_fwd", 40 + j, 0.021 + 10e-3 * j, 4e-3)
            for j in range(6)]
    ops.append(_kernel("flash_fwd", 60, 0.085, 6e-3))
    ops += [_kernel("moe_gmm", 90 + j, 0.092 + 1e-3 * j, 0.9e-3)
            for j in range(3)]
    host.append(E(spans.PREFILL, 0.019, 1e-4, {
        "tokens": 3000, "bucket": 4096, "rid": "x", "new_program": 0,
        "scan_chunks": 47}))
    for evs in (ops, modules, host):
        evs.sort(key=lambda e: e.start)
    return {"trace": xplane.Trace({0: modules}, {0: ops}, {}, {}),
            "model": model, "sizes": sz, "cfg": cfg,
            "peaks": PEAKS["TPU v5 lite"], "result": {"traced": {}},
            "_spans": spans.Reading(host, {}, 0.0)}


def test_step_roofline_time_and_share_read_the_recurrences_kernel(
        traced_run):
    run = traced_run
    need = run["model"].kda_step_call(run["sizes"], 64)
    want = 100 * (need["bytes"] / 819e9) / (12 * 0.25e-3)
    assert metric("kernel.kda_step_roofline.docs16k")(run) == \
        pytest.approx(want, rel=1e-6)
    assert 0 < want < 100
    assert metric("step.attn_kda_ms.docs16k")(run) == pytest.approx(
        6 * 0.25)
    # a step whose span fell outside the trace: its six kernels are there,
    # and what the spans require is scaled to the kernels counted
    run["trace"].ops[0].extend(
        _kernel("kda_step", 80 + j, 0.2 + 1e-3 * j, 0.25e-3)
        for j in range(6))
    assert metric("kernel.kda_step_roofline.docs16k")(run) == \
        pytest.approx(want, rel=1e-6)
    # Olmo's readers do not take this kernel for theirs
    assert metric("kernel.delta_step_roofline.answers3k")(run) is None
    assert metric("step.attn_linear_ms.answers3k")(run) is None
    assert metric("kernel.delta_chunk_roofline.answers3k")(run) is None


def test_chunk_roofline_counts_true_tokens(traced_run):
    run = traced_run
    need = run["model"].kda_chunk_call(run["sizes"], 3000)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert metric("kernel.kda_chunk_roofline.docs16k")(run) == \
        pytest.approx(100 * least / (6 * 4e-3), rel=1e-6)


def test_state_bytes_share_is_state_over_state_and_latent_rows(traced_run):
    state = 2 * 32 * 2 * 6 * (STATE + TAIL)
    rows = 2 * 90256 * 576 * 2 * 1
    assert metric("cache.state_bytes_share.docs16k")(traced_run) == \
        pytest.approx(100 * state / (state + rows))


def test_the_accepted_readers_find_their_numbers_in_the_same_trace(
        traced_run):
    run = traced_run
    model, sz = run["model"], run["sizes"]
    assert metric("step.decode_ms.batch")(run) == pytest.approx(9.0)
    assert metric("step.prefill_ms.answers3k")(run) == pytest.approx(80.0)
    assert metric("step.attn_latent_ms.reason4k")(run) == pytest.approx(0.3)
    assert metric("step.moe_gmm_ms.reason4k")(run) == pytest.approx(
        18 * 0.2)
    mla = model.mla_decode_call(sz, 180000, 64)
    assert metric("kernel.mla_decode_roofline.batch32")(run) == \
        pytest.approx(100 * (mla["bytes"] / 819e9) / (2 * 0.3e-3), rel=1e-6)
    gmm = model.moe_gmm_call(sz, 760, 420)
    assert metric("kernel.moe_gmm_roofline.batch32")(run) == \
        pytest.approx(100 * (gmm["bytes"] / 819e9) / (36 * 0.2e-3),
                      rel=1e-6)
    flash = model.flash_prefill_call(sz, 3000)
    assert metric("kernel.flash_mla_roofline.reason4k")(run) == \
        pytest.approx(100 * max(flash["flops"] / 197e12,
                                flash["bytes"] / 819e9) / 6e-3, rel=1e-6)
    # six expert layers of 128 held experts, two steps
    assert metric("moe.pairs_per_held_expert.agent8k")(run) == \
        pytest.approx(760 / (128 * 6 * 2))
    assert metric("moe.experts_touched_share.agent8k")(run) == \
        pytest.approx(100 * 420 / (128 * 6 * 2))
    assert metric("moe.load_max_over_mean.reason4k")(run) == \
        pytest.approx(80 * 128 / 760)


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_leave_the_line_where_there_is_nothing_to_read(
        traced_run, name):
    """The parent's program (no such kernel, no such attribute), a dense
    model's module and file, an untraced run: None, nothing raised."""
    run = dict(traced_run)
    dense = modelcfg.load_config("internlm2-1.8b")
    plain = [E(spans.DISPATCH, 0.0, 1e-4, {"lanes": 8, "live_positions": 9,
                                            "read_positions": 16}),
             E(spans.PREFILL, 0.02, 1e-4, {"tokens": 9, "bucket": 16})]
    ops = [_kernel("paged_decode_attn", 0, 1e-3, 1e-4),
           _kernel("flash_fwd", 1, 0.02, 1e-4)]
    run.update(cfg=dense, model=modelcfg.load_model(dense),
               sizes=modelcfg.load_model(dense).sizes(dense),
               trace=xplane.Trace({0: [E("jit__step(7)", 0.0, 0.008)]},
                                  {0: ops}, {}, {}),
               _spans=spans.Reading(plain, {}, 0.0))
    assert metric(name)(run) is None
    run.update(trace=None, _spans=None)
    assert metric(name)(run) is None


def test_the_cell_is_listed_where_its_readers_find_something():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # the ninth cell and the eighth configuration; later PRs append
    assert bench["workloads"][8]["name"] == CELL
    assert bench["configs"][7]["name"] == LING
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert set(NEW) | set(SHARED) <= listed
    assert {"serve_tokens_per_s", "setup_s"} <= listed
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
    # appended together in PR 50; later PRs append behind them
    names = [m["name"] for m in bench["per_layer"]]
    assert names[names.index(NEW[0]):][:4] == NEW
    # readers that return None for this model are left off
    off = {"kernel.delta_step_roofline.answers3k", "step.moe_ms.batch32",
           "kernel.full_decode_roofline.mixed8k", "step.ssm_ms.agent8k",
           "moe.zero_pairs_share.reason4k",
           "cache.state_bytes_share.answers3k",
           "cache.state_bytes_share.agent8k"}
    assert not off & listed
