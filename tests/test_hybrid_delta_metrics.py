"""The readers of the per-layer metrics PR 37 added for the cell
`olmo-hybrid-7b-1chip.serve.answers3k`, on a trace built by hand: each
reads what its docstring says, and leaves the line (None, nothing raised)
where the program writes no such span or kernel: the parent's program,
another model's module, an untraced run. Also the required operations and
bytes of the two new kernels, and that BENCHMARK.json lists the cell
where its readers find something."""
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
OLMO = "olmo-hybrid-7b-1chip"
CELL = OLMO + ".serve.answers3k"
NEMOTRON_CELL = "nemotron-3-super-120b-a12b-1chip.serve.agent8k"
LING_CELL = "ling-3.0-flash-vl-1chip.serve.docs16k"
NEW = ["kernel.delta_step_roofline.answers3k",
       "kernel.delta_chunk_roofline.answers3k",
       "step.attn_linear_ms.answers3k", "step.prefill_ms.answers3k",
       "cache.state_bytes_share.answers3k"]
STATE = 96 * 5760 * 4           # a layer's state of one sequence, bytes
TAIL = 3 * 11520 * 2


def metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _kernel(name, i, start, dur):
    return E(f"%{name}.{i} = f32[32,1,5760] custom-call(...), "
             f"custom_call_target=\"tpu_custom_call\"", start, dur)


@pytest.fixture()
def traced_run():
    """Two decode steps of 16 ms from t = 0 and t = 0.1 with a prefill of
    60 ms between them. In a step a layer begins every 1.2 ms with its
    mixer's kernel (0.4 ms the recurrence, 0.9 ms full attention); in the
    prefill each linear layer's chunk kernel takes 1.5 ms."""
    cfg = modelcfg.load_config(OLMO)
    model = modelcfg.load_model(cfg)
    ops, modules, steps = [], [], []
    for t0 in (0.0, 0.1):
        modules.append(E("jit__step(7)", t0, 0.016))
        for layer, kind in enumerate(cfg["layer_types"]):
            full = kind == "full_attention"
            ops.append(_kernel(
                "paged_decode_attn" if full else "gated_delta_step", layer,
                t0 + 1.2e-3 * layer, 0.9e-3 if full else 0.4e-3))
        steps.append(E(spans.DISPATCH, t0, 1e-4, {
            "lanes": 32, "live_positions": 40000, "read_positions": 40256,
            "state_slots": 32, "state_bytes": 32 * 2 * 9 * (STATE + TAIL)}))
    modules.append(E("jit__pre(9)", 0.02, 0.060))
    ops += [_kernel("gated_delta_chunk_fwd", 40 + j, 0.021 + 5e-3 * j,
                    1.5e-3) for j in range(9)]
    ops += [_kernel("flash_fwd", 60 + j, 0.025 + 15e-3 * j, 2e-3)
            for j in range(3)]
    steps.append(E(spans.PREFILL, 0.019, 1e-4, {
        "tokens": 1300, "bucket": 2048, "rid": "x", "new_program": 0,
        "scan_chunks": 21}))
    ops.sort(key=lambda e: e.start)
    modules.sort(key=lambda e: e.start)
    steps.sort(key=lambda e: e.start)
    return {"trace": xplane.Trace({0: modules}, {0: ops}, {}, {}),
            "model": model, "sizes": model.sizes(cfg), "cfg": cfg,
            "peaks": PEAKS["TPU v5 lite"], "result": {"traced": {}},
            "_spans": spans.Reading(steps, {}, 0.0)}


def test_required_operations_and_bytes_of_the_new_kernels():
    cfg = modelcfg.load_config(OLMO)
    model = modelcfg.load_model(cfg)
    sz = model.sizes(cfg)
    assert model.param_count(sz) == cfg["parameters"] == 3268268508
    # 9 linear layers: a state in and out, q k v (bf16), two gates and the
    # float32 outputs a lane-step
    step = model.delta_step_call(sz, 32)
    assert step["bytes"] == 9 * 32 * (2 * STATE + 11520 * 2 + 60 * 4
                                      + 5760 * 4)
    assert step["flops"] == 9 * 32 * 7.0 * 30 * 96 * 192
    # bytes bound it: 1.29 GB a step at 32 lanes, 1.57 ms at 819 GB/s
    assert step["bytes"] / 819e9 > step["flops"] / 197e12
    chunk = model.delta_chunk_call(sz, 1280)
    per = (2 * 64 * 64 * 96 + 64 ** 3 / 3 + 64 * 64 * (2 * 192 + 96)
           + 6 * 64 * 96 * 192)
    assert chunk["flops"] == pytest.approx(9 * 30 * 20 * per)
    assert chunk["bytes"] == 9 * (1280 * (11520 + 5760) * 2
                                  + 1280 * 60 * 4 + STATE)
    # 3 full layers of 30 heads over 30 kv heads of 128
    full = model.full_decode_call(sz, 1000, 32)
    assert full["bytes"] == 3 * 2 * (2 * 1000 * 3840 + 2 * 32 * 3840)
    assert full["flops"] == 3 * 4.0 * 1000 * 3840
    assert model.matmul_params(sz) < model.param_count(sz)
    assert model.train_flops_per_token(sz, 2048) > 6 * model.matmul_params(
        sz)


def test_step_roofline_time_and_share_read_the_recurrences_kernel(
        traced_run):
    run = traced_run
    need = run["model"].delta_step_call(run["sizes"], 64)
    want = 100 * (need["bytes"] / 819e9) / (18 * 0.4e-3)
    assert metric("kernel.delta_step_roofline.answers3k")(run) == \
        pytest.approx(want, rel=1e-6)
    assert 0 < want < 100
    assert metric("step.attn_linear_ms.answers3k")(run) == pytest.approx(
        9 * 0.4)
    # the cell's general readers find their numbers in the same trace
    assert metric("step.attn_full_ms.mixed8k")(run) == pytest.approx(
        3 * 0.9)
    full = run["model"].full_decode_call(run["sizes"], 80000, 64)
    assert metric("kernel.full_decode_roofline.mixed8k")(run) == \
        pytest.approx(100 * (full["bytes"] / 819e9) / (6 * 0.9e-3),
                      rel=1e-6)
    assert metric("step.decode_ms.batch")(run) == pytest.approx(16.0)
    assert metric("engine.kv_live_share.batch")(run) == pytest.approx(
        100 * 40000 / 40256)
    # a step whose span fell outside the trace: its nine kernels are
    # there, and what the spans require is scaled to the kernels counted
    run["trace"].ops[0].extend(
        _kernel("gated_delta_step", 80 + j, 0.2 + 1e-3 * j, 0.4e-3)
        for j in range(9))
    assert metric("kernel.delta_step_roofline.answers3k")(run) == \
        pytest.approx(want, rel=1e-6)


def test_chunk_roofline_counts_true_tokens_and_prefill_time_is_the_programs(
        traced_run):
    run = traced_run
    need = run["model"].delta_chunk_call(run["sizes"], 1300)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert metric("kernel.delta_chunk_roofline.answers3k")(run) == \
        pytest.approx(100 * least / (9 * 1.5e-3), rel=1e-6)
    assert metric("step.prefill_ms.answers3k")(run) == pytest.approx(60.0)


def test_state_bytes_share_is_state_over_state_and_keys_and_values(
        traced_run):
    state = 2 * 32 * 2 * 9 * (STATE + TAIL)
    kv = 2 * 40256 * 2 * 3840 * 2 * 3
    assert metric("cache.state_bytes_share.answers3k")(traced_run) == \
        pytest.approx(100 * state / (state + kv))


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
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert set(NEW) <= listed and "serve_tokens_per_s" in listed
    assert {"setup_s", "step.decode_ms.batch", "step.attn_full_ms.mixed8k",
            "kernel.full_decode_roofline.mixed8k",
            "engine.kv_live_share.batch"} <= listed
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            # the delta rule's readers are this cell's alone; the
            # prefill's reads any model that hands up `prefill_counts`
            # (the cells of later PRs are appended behind them)
            shares = ([NEMOTRON_CELL, LING_CELL]
                      if m["name"] == "step.prefill_ms.answers3k" else [])
            assert m["workloads"][:1 + len(shares)] == [CELL] + shares
            assert shares or m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
