"""The readers of the per-layer metrics PR 35 added for the cell
`laguna-xs.2-1chip.serve.mixed8k`, on a trace built by hand: each reads
what its docstring says, and leaves the line (None, nothing raised) where
the program writes no such span or kernel: the parent's program, another
model's module, an untraced run."""
import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
BENCH = os.path.join(ROOT, "benchmarks")

from benchmarks.harness import modelcfg, spans, xplane       # noqa: E402
from benchmarks.harness.peaks import PEAKS                   # noqa: E402

E = xplane.Event
LAGUNA = "laguna-xs.2-1chip"
NEW = ["kernel.window_decode_roofline.mixed8k",
       "kernel.full_decode_roofline.mixed8k",
       "kernel.flash_window_roofline.mixed8k", "step.attn_full_ms.mixed8k",
       "step.attn_window_ms.mixed8k", "step.moe_ms.mixed8k",
       "cache.window_read_share.mixed8k",
       "moe.experts_touched_share.mixed8k",
       "moe.load_max_over_mean.mixed8k"]


def metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _kernel(name, i, start, dur):
    return E(f"%{name}.{i} = bf16[32,2048] custom-call(...), "
             f"custom_call_target=\"tpu_custom_call\"", start, dur)


@pytest.fixture()
def traced_run():
    """Two decode steps of 8 ms from t = 0 and t = 0.03 with a prefill of
    20 ms between them. In a step a layer begins every 1.5 ms with its
    attention kernel (0.3 ms a full layer, 0.1 ms a sliding one) and, in
    the four expert layers, three grouped matmuls of 0.3 ms behind it."""
    cfg = modelcfg.load_config(LAGUNA)
    model = modelcfg.load_model(cfg)
    ops, modules, steps = [], [], []
    kinds = cfg["layer_types"]
    for t0 in (0.0, 0.03):
        modules.append(E("jit__step(7)", t0, 0.008))
        for layer, kind in enumerate(kinds):
            t = t0 + 1.5e-3 * layer
            full = kind == "full_attention"
            ops.append(_kernel(
                "paged_decode_attn" if full else "paged_window_decode_attn",
                layer, t, 0.3e-3 if full else 0.1e-3))
            for j in range(3 if layer else 0):
                ops.append(_kernel("moe_gmm", 3 * layer + j,
                                   t + 0.35e-3 * (j + 1), 0.3e-3))
        steps.append(E(spans.DISPATCH, t0, 1e-4, {
            "lanes": 32, "live_positions": 128000, "read_positions": 128256,
            "window_positions_live": 16000, "window_positions_read": 16700}))
        steps.append(E("engine.emit", t0 + 0.009, 1e-4, {
            "moe_pairs": 1024, "moe_experts_touched": 600,
            "moe_load_max": 20}))
    modules.append(E("jit__pre(9)", 0.009, 0.020))
    ops += [_kernel("flash_window_fwd", 40 + j, 0.010 + 3e-3 * j, 1e-3)
            for j in range(3)]
    ops += [_kernel("flash_fwd", 50 + j, 0.011 + 9e-3 * j, 2e-3)
            for j in range(2)]
    ops += [_kernel("moe_gmm", 90 + j, 0.020 + 1e-3 * j, 0.9e-3)
            for j in range(3)]
    steps.append(E(spans.PREFILL, 0.0085, 1e-4, {
        "tokens": 3000, "bucket": 4096, "rid": "x", "new_program": 0}))
    ops.sort(key=lambda e: e.start)
    modules.sort(key=lambda e: e.start)
    steps.sort(key=lambda e: e.start)
    return {"trace": xplane.Trace({0: modules}, {0: ops}, {}, {}),
            "model": model, "sizes": model.sizes(cfg), "cfg": cfg,
            "peaks": PEAKS["TPU v5 lite"], "result": {"traced": {}},
            "_spans": spans.Reading(steps, {}, 0.0)}


def test_decode_rooflines_read_each_kinds_kernel_and_positions(traced_run):
    run = traced_run
    full = run["model"].full_decode_call(run["sizes"], 256000, 64)
    want = 100 * (full["bytes"] / 819e9) / (4 * 0.3e-3)
    assert metric("kernel.full_decode_roofline.mixed8k")(run) == \
        pytest.approx(want, rel=1e-6)
    ring = run["model"].window_decode_call(run["sizes"], 32000, 64)
    want = 100 * (ring["bytes"] / 819e9) / (6 * 0.1e-3)
    assert metric("kernel.window_decode_roofline.mixed8k")(run) == \
        pytest.approx(want, rel=1e-6)
    assert 0 < want < 100


def test_flash_window_roofline_counts_true_tokens_of_the_traced_prefills(
        traced_run):
    run = traced_run
    need = run["model"].flash_window_call(run["sizes"], 3000)
    want = 100 * (need["flops"] / PEAKS["TPU v5 lite"]["bf16_flops"]) / 3e-3
    assert metric("kernel.flash_window_roofline.mixed8k")(run) == \
        pytest.approx(want, rel=1e-6)
    # a prefill whose span fell outside the trace: its three kernels are
    # there, and what the spans require is scaled to the kernels counted
    run["trace"].ops[0].extend(
        _kernel("flash_window_fwd", 60 + j, 0.05 + 2e-3 * j, 1e-3)
        for j in range(3))
    assert metric("kernel.flash_window_roofline.mixed8k")(run) == \
        pytest.approx(want, rel=1e-6)


def test_step_times_by_kind_and_the_experts_between_attention_kernels(
        traced_run):
    run = traced_run
    assert metric("step.attn_full_ms.mixed8k")(run) == pytest.approx(0.6)
    assert metric("step.attn_window_ms.mixed8k")(run) == pytest.approx(0.3)
    # from a kernel's end to the next layer's start: 1.4 ms after a sliding
    # layer, 1.2 after a full one; layers 1-3 have a next layer, the fourth
    # expert layer is taken as their mean
    assert metric("step.moe_ms.mixed8k")(run) == pytest.approx(
        4 * 1.4, rel=1e-6)


def test_window_read_share_and_expert_counts(traced_run):
    run = traced_run
    assert metric("cache.window_read_share.mixed8k")(run) == pytest.approx(
        100 * 16700 / 128256)
    assert metric("moe.experts_touched_share.mixed8k")(run) == \
        pytest.approx(100 * 1200 / (256 * 4 * 2))
    assert metric("moe.load_max_over_mean.mixed8k")(run) == pytest.approx(
        40 * 256 / 2048)
    # the cell's general readers find their numbers in the same trace
    assert metric("engine.kv_live_share.batch")(run) == pytest.approx(
        100 * 128000 / 128256)
    gmm = run["model"].moe_gmm_call(run["sizes"], 2048, 1200)
    assert metric("kernel.moe_gmm_roofline.batch32")(run) == pytest.approx(
        100 * (gmm["bytes"] / 819e9) / (24 * 0.3e-3), rel=1e-6)


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_leave_the_line_where_there_is_nothing_to_read(
        traced_run, name):
    """The parent's program (no window kernel, no window attribute), a
    dense model's module and file, an untraced run: None, nothing raised."""
    run = dict(traced_run)
    dense = modelcfg.load_config("internlm2-1.8b")
    plain = [E(spans.DISPATCH, 0.0, 1e-4, {"lanes": 8, "live_positions": 9,
                                            "read_positions": 16}),
             E("engine.emit", 0.01, 1e-4, {}),
             E(spans.PREFILL, 0.02, 1e-4, {"tokens": 9, "bucket": 16})]
    ops = [_kernel("paged_decode_attn", 0, 1e-3, 1e-4),
           _kernel("flash_fwd", 1, 0.02, 1e-4)]
    run.update(cfg=dense, model=modelcfg.load_model(dense),
               sizes=modelcfg.load_model(dense).sizes(dense),
               trace=xplane.Trace({0: [E("jit__step(7)", 0.0, 0.008)]},
                                  {0: ops}, {}, {}),
               _spans=spans.Reading(plain, {}, 0.0))
    if name != "step.attn_full_ms.mixed8k":     # the dense step has one
        assert metric(name)(run) is None
    run.update(trace=None, _spans=None)
    assert metric(name)(run) is None
