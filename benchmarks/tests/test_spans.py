"""The program's spans read back from the recorded engine trace
(benchmarks/tests/data/record_engine_trace.py printed the numbers used
here): a small `LLMEngine` on a v5e serving a request of 5 tokens, idle for
0.12 s, serving one of 20 tokens, then one gradient of an attention layer."""
import importlib.util
import os
import shutil

import pytest

from benchmarks.harness import modelcfg, spans, xplane
from benchmarks.harness.peaks import PEAKS

Sizes = modelcfg.load_model({"model": "dense_gqa"}).Sizes

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "tiny_engine_v5e.xplane.pb")
NO_SPANS = os.path.join(HERE, "data", "tiny_v5e.xplane.pb")
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
SERVING = ["engine.host_gap_ms_per_step.chat",
           "engine.host_gap_ms_per_step.batch",
           "engine.gap_publish_ms_per_step.batch",
           "engine.gap_fetch_ms_per_step.batch",
           "engine.gap_tables_ms_per_step.batch",
           "engine.no_work_share.chat", "engine.lanes_share.batch",
           "engine.kv_live_share.batch", "engine.prefill_pad_share.chat"]
KERNELS = ["kernel.flash_fwd_roofline.train",
           "kernel.flash_bwd_roofline.train"]


@pytest.fixture(scope="module")
def by_thread():
    return spans.load(DATA)


@pytest.fixture(scope="module")
def reading():
    return spans.read(xplane.load(DATA), DATA)


def metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def a_run(tmp_path, pb, traced=True):
    """What run.py hands a metric, for a run that traced `pb`."""
    run = {"trace": None, "result": {"traced": None},
           "cfg": {"deployment": {"max_batch": 2}},
           "samples": {"batch": 1, "seq_len": 256},
           "sizes": Sizes(vocab=512, d_model=256, layers=1, heads=2,
                          kv_heads=1, head_dim=128, d_ff=512,
                          rope_theta=1e4, norm_eps=1e-5, tied=False),
           "peaks": PEAKS["TPU v5 lite"]}
    if traced:
        d = tmp_path / "plugins" / "profile" / "run"
        d.mkdir(parents=True)
        shutil.copy(pb, d / "vm.xplane.pb")
        run["trace"] = xplane.load(pb)
        # these recordings predate the window's marker: the device's span
        # stands in for what run.py reads with `xplane.traced_window`
        lo, hi = xplane.device_span(run["trace"])
        run["result"]["traced"] = {"dir": str(tmp_path), "lo": lo, "hi": hi,
                                   "window_s": hi - lo}
    return run


def test_step_thread_nesting_and_attributes(by_thread):
    thread = spans.step_thread(by_thread)
    assert thread is not None and len(by_thread) == 2
    mine = by_thread[thread]
    above = {s.name: (p.name if p else None)
             for s, p in zip(mine, spans.parents(mine))}
    assert above == {
        "engine.wait_for_work": None, "engine.step": None,
        "engine.prefill": "engine.step", "engine.page_tables": "engine.step",
        "engine.decode_dispatch": "engine.step",
        "engine.fetch_tokens": "engine.step", "engine.emit": "engine.step",
        "engine.ingest": None, "stream.publish": "engine.ingest",

        "engine.yield": None}
    pre = [s.stats for s in mine if s.name == "engine.prefill"]
    assert pre == [
        {"rid": "a", "tokens": 5, "bucket": 16, "new_program": 0},
        {"rid": "b", "tokens": 20, "bucket": 32, "new_program": 0}]
    disp = [s.stats for s in mine if s.name == "engine.decode_dispatch"]
    assert disp == [
        {"lanes": 1, "live_positions": n, "read_positions": 512}
        for n in (6, 7, 21)]
    steps = [s.stats for s in mine if s.name == "engine.step"]
    assert [a["step"] for a in steps] == [4, 5, 6]
    assert all(a["t_mono_ns"] > 0 for a in steps)
    # the other thread is the caller's
    (other,) = [t for t in by_thread if t != thread]
    assert [(s.name, s.stats) for s in by_thread[other]] == [
        ("engine.submit", {"rid": "a"}), ("engine.submit", {"rid": "b"})]


def test_gap_pieces_add_up_to_the_idle_time(reading):
    gaps = xplane.idle_gaps(xplane.load(DATA))
    total = sum(b - a for a, b in gaps)
    assert reading.idle_s == pytest.approx(total, rel=1e-9)
    assert sum(reading.gaps.values()) == pytest.approx(total, rel=1e-6)
    # the idle stretch between the two requests is the engine's wait
    assert max(reading.gaps, key=reading.gaps.get) == "engine.wait_for_work"
    assert reading.gaps["engine.wait_for_work"] > 0.1
    assert reading.gap_s("engine.ingest", "stream.publish") == pytest.approx(
        reading.gaps["engine.ingest"] + reading.gaps["stream.publish"])


def test_split_cuts_at_span_boundaries_innermost_first():
    E = xplane.Event
    mine = [E("engine.step", 1.0, 4.0), E("engine.fetch_tokens", 2.0, 1.0),
            E("engine.yield", 6.0, 1.0)]
    # busy to 0.5, from 2.5 to 2.75 and from 6.5: two gaps
    device = xplane.Trace({}, {0: [E("a", 0.0, 0.5), E("b", 2.5, 0.25),
                                   E("c", 6.5, 0.5)]}, {}, {})
    got = spans.split_gaps(device, mine)
    assert got == pytest.approx({
        "outside": 0.5 + 1.0, "engine.step": 1.0 + 2.0,
        "engine.fetch_tokens": 0.5 + 0.25, "engine.yield": 0.5})


@pytest.mark.parametrize("name", SERVING + KERNELS)
def test_metric_reads_a_number_from_the_trace(tmp_path, name):
    value = metric(name)(a_run(tmp_path, DATA))
    assert isinstance(value, float) and value >= 0.0
    if name.endswith(("_share.chat", "_share.batch", "_roofline.train")):
        assert value <= 100.0


@pytest.mark.parametrize("name", SERVING + KERNELS + [
    # the recorded engine predates the paged kernel: dispatches, no event
    "kernel.paged_decode_roofline.batch"])
def test_metric_is_left_out_where_there_is_nothing_to_read(tmp_path, name):
    read = metric(name)
    assert read(a_run(tmp_path, DATA, traced=False)) is None
    # a trace of a program that writes no span and names no kernel
    assert read(a_run(tmp_path, NO_SPANS)) is None


def test_counts_from_the_attributes(tmp_path):
    run = a_run(tmp_path, DATA)
    assert metric("engine.lanes_share.batch")(run) == pytest.approx(50.0)
    assert metric("engine.kv_live_share.batch")(run) == pytest.approx(
        100.0 * (6 + 7 + 21) / (3 * 512))
    assert metric("engine.prefill_pad_share.chat")(run) == pytest.approx(
        100.0 * (1 - 25 / 48))


def test_kernels_are_found_by_their_names():
    ops = xplane.op_times(xplane.load(DATA))
    for kernel in ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq",
                   "rms_norm_fwd"):
        assert ops["kernel:" + kernel] > 0
    assert not any(k.startswith("kernel:") and k.split(":")[1] in (
        "closed_call", "checkpoint", "rematted_computation") for k in ops)
