"""`harness/op_scopes.py`: the wire reader on bytes built by hand and on the
recorded traces, an operation's region and pass from its path, and the
region metrics on a trace without regions (`tiny_engine_v5e.xplane.pb`,
recorded before they existed) and on one with them (`regions_v5e.xplane.pb`;
`data/record_regions_trace.py` printed the numbers used here: a tiny
engine's steps and five steps of a tiny rematted loss + AdamW)."""
import importlib.util
import json
import os
import shutil
import struct
import subprocess
import sys

import pytest

from benchmarks.harness import op_scopes, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BEFORE = os.path.join(HERE, "data", "tiny_engine_v5e.xplane.pb")
REGIONS = os.path.join(HERE, "data", "regions_v5e.xplane.pb")
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    NEW_METRICS = [m["name"] for m in json.load(f)["per_layer"]
                   if m["name"].startswith(("region.", "train."))]
ENGINE_REGIONS = {"r.embed", "r.norm", "r.attn_in", "r.attn_core",
                  "r.attn_out", "r.ffn", "r.head", "r.cache"}


def metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def a_run(tmp_path, pb):
    """What run.py hands a metric, for a run that traced `pb`."""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(pb, d / "vm.xplane.pb")
    return {"trace": xplane.load(pb),
            "result": {"traced": {"dir": str(tmp_path)}}}


# ------------------------------------------------------------ the wire
def varint(value: int) -> bytes:
    out = bytearray()
    while True:
        value, low = value >> 7, value & 0x7F
        out.append(low | (0x80 if value else 0))
        if not value:
            return bytes(out)


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, float):
        return varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def entry(key: int, message: bytes) -> bytes:
    return field(1, key) + field(2, message)


def a_space() -> bytes:
    """One device plane: a module execution of 100 ns holding a `while` of
    60 with a matmul of 40 inside it, then an operation of another
    program's; `tf_op` once as a string and once as a reference."""
    stat_names = {1: "tf_op", 2: "program_id", 3: "flops", 4: "source",
                  5: "jit(_step)/r.ffn/dot_general"}
    step = "jit__step(77)"
    metas = {
        1: field(2, step),
        2: field(2, "%while.1 = while(...)") + field(5, field(1, 1) + field(
            5, "jit(_step)/while")) + field(5, field(1, 2) + field(3, 77)),
        3: field(2, "%fusion.2 = fusion(...)") + field(5, field(1, 1)
                                                        + field(7, 5))
        + field(5, field(1, 2) + field(3, 77)) + field(5, field(1, 3)
                                                        + field(2, 8e9))
        + field(5, field(1, 4) + field(5, "moe.py:304")),
        4: field(2, "%copy.3 = copy(...)") + field(5, field(1, 2)
                                                    + field(3, 99))}

    def event(meta, offset_ps, dur_ps):
        return field(1, meta) + field(2, offset_ps) + field(3, dur_ps)
    ops = (field(2, "XLA Ops") + field(3, 5)
           + field(4, event(2, 10_000, 60_000))
           + field(4, event(3, 20_000, 40_000))
           + field(4, event(4, 80_000, 5_000)))
    modules = (field(2, "XLA Modules") + field(3, 5)
               + field(4, event(1, 0, 100_000)))
    other = field(2, "Async XLA Ops") + field(4, event(3, 0, 100_000))
    plane = (field(2, "/device:TPU:0") + field(3, ops) + field(3, modules)
             + field(3, other)
             + b"".join(field(4, entry(k, field(1, k) + v))
                        for k, v in metas.items())
             + b"".join(field(5, entry(k, field(1, k) + field(2, v)))
                        for k, v in stat_names.items()))
    host = field(2, "/host:CPU") + field(3, field(2, "python"))
    return field(1, host) + field(1, plane)


def test_the_wire_reader_on_bytes_built_by_hand(tmp_path):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(a_space())
    dev = op_scopes.load(str(path))
    assert [op.meta.name.split(" ")[0] for op in dev.ops] == [
        "%while.1", "%fusion.2", "%copy.3"]
    matmul = dev.ops[1].meta
    assert matmul.tf_op == "jit(_step)/r.ffn/dot_general"    # by reference
    assert dev.ops[0].meta.tf_op == "jit(_step)/while"       # as a string
    assert (matmul.program_id, matmul.flops, matmul.source) == (
        77, 8e9, "moe.py:304")
    [ex] = dev.executions
    assert (ex.program, ex.program_id, ex.dur_ps) == ("jit__step", 77,
                                                      100_000)
    # self times: the loop holds the matmul; the other program's operation
    # is left out
    assert [(m.name.split(" ")[0], own) for m, own in ex.ops] == [
        ("%while.1", 20_000), ("%fusion.2", 40_000)]
    assert op_scopes.table(dev, "jit__step") == {
        ("r.ffn", "plain"): pytest.approx(40_000e-9),
        (op_scopes.UNSCOPED, "plain"): pytest.approx(20_000e-9)}
    assert op_scopes.table(dev, "jit__pre") is None


def test_a_file_without_the_device_plane_reads_none(tmp_path):
    path = tmp_path / "cpu.xplane.pb"
    path.write_bytes(field(1, field(2, "/host:CPU")))
    assert op_scopes.load(str(path)) is None
    path.write_bytes(b"")
    assert op_scopes.load(str(path)) is None


# ------------------------------------------------------------ the path
@pytest.mark.parametrize("path,region,which", [
    ("jit(_step)/r.ffn/dot_general", "r.ffn", "plain"),
    ("jit(_step)/r.attn_in/r.attn_core/paged_decode_attn/pallas_call",
     "r.attn_core", "plain"),
    ("jit(loss)/transpose(jvp(r.ffn))/dot_general", "r.ffn", "backward"),
    ("jit(_step)/jvp(r.head)/rms_norm_fwd/pallas_call:", "r.head",
     "forward"),
    ("jit(_step)/jvp()/while/body/closed_call/r.norm/mul", "r.norm",
     "forward"),
    ("jit(_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/r.attn_out/transpose", "r.attn_out",
     "recompute"),
    ("jit(_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "r.attn_core/flash_bwd_dkdv/pallas_call", "r.attn_core", "backward"),
    ("jit(_step)/add", op_scopes.UNSCOPED, "plain"),
    ("jit(norm)/jit(r_norm)/mul", op_scopes.UNSCOPED, "plain"),
    ("", op_scopes.UNSCOPED, "plain"),
])
def test_region_and_pass_of_a_path(path, region, which):
    assert op_scopes.region_of(path) == region
    assert op_scopes.pass_of(path) == which


# ------------------------------------------------- a trace from before
@pytest.fixture(scope="module")
def before():
    return op_scopes.load(BEFORE)


def test_the_recorded_engine_trace_carries_tf_op(before):
    total = sum(op.dur_ps for op in before.ops)
    named = sum(op.dur_ps for op in before.ops if op.meta.tf_op)
    assert named / total == pytest.approx(0.797, abs=0.001)
    norm = next(op.meta for op in before.ops
                if op.meta.name.startswith("%rms_norm_fwd.6 ="))
    assert norm.tf_op.startswith(
        "jit(_pre)/while/body/closed_call/rms_norm/rms_norm_fwd/")
    assert norm.source.endswith("ray_tpu/ops/norms.py:68")
    assert norm.hlo_category == "custom-call"
    pre = next(ex for ex in before.executions if ex.program == "jit__pre")
    assert norm.program_id == pre.program_id == 11800079773899537807
    assert {ex.program for ex in before.executions} >= {
        "jit__step", "jit__pre", "jit_loss"}


def test_operations_agree_with_profile_data(before):
    """The same events as `xplane.load` reads through `ProfileData`, which
    cuts each time to whole nanoseconds."""
    trace = xplane.load(BEFORE)
    assert len(before.ops) == len(trace.ops[0])
    assert sum(op.dur_ps for op in before.ops) * 1e-12 == pytest.approx(
        sum(e.dur for e in trace.ops[0]), rel=5e-3)
    steps = xplane.program_times(trace)["jit__step"]
    assert [ex.dur_ps * 1e-12 for ex in op_scopes.executions(
        before, "jit__step")] == pytest.approx(steps, rel=1e-3)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_regions_reads_none(tmp_path, name):
    assert len(NEW_METRICS) == 19
    assert metric(name)(a_run(tmp_path, BEFORE)) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_an_untraced_run_reads_none(name):
    assert metric(name)({"trace": None, "result": {"traced": None}}) is None


# ------------------------------------------------- a trace with regions
@pytest.fixture(scope="module")
def after():
    return op_scopes.load(REGIONS)


def test_a_decode_steps_regions_add_up_to_the_program(after):
    table = op_scopes.table(after, "jit__step")
    assert {region for region, _ in table} - {op_scopes.UNSCOPED} == (
        ENGINE_REGIONS)
    assert {which for _, which in table} == {"plain"}
    runs = op_scopes.executions(after, "jit__step")
    for ex in runs:
        by = op_scopes.by_region_and_pass(ex)
        own = sum(t for _, t in ex.ops) * 1e-9
        assert sum(by.values()) == pytest.approx(own)
        scoped = sum(ms for (region, _), ms in by.items()
                     if region != op_scopes.UNSCOPED)
        assert scoped == pytest.approx(
            own - by.get((op_scopes.UNSCOPED, "plain"), 0.0))
        # the operations fill the program's execution
        assert own <= ex.dur_ps * 1e-9 * (1 + 1e-9)
        assert own >= 0.9 * ex.dur_ps * 1e-9


def test_the_engines_sampling_is_its_own_region(after):
    # (beside it, under no region, XLA's own copy of the result)
    assert ("r.sample", "plain") in op_scopes.table(after, "jit__next")
    assert ("r.sample", "plain") in op_scopes.table(after, "jit__place")


def test_a_training_step_shows_all_four_passes(after):
    table = op_scopes.table(after, "jit__train_step")
    assert len(op_scopes.executions(after, "jit__train_step")) == 5
    by_pass = {which: sum(ms for (_, w), ms in table.items() if w == which)
               for which in op_scopes.PASSES}
    assert all(ms > 0 for ms in by_pass.values()), by_pass
    assert by_pass["backward"] > by_pass["forward"] > by_pass["recompute"]
    # the optimiser is the step's own: under no region
    assert {region for region, which in table if which == "plain"} == {
        op_scopes.UNSCOPED}
    assert ("r.ffn", "backward") in table and ("r.ffn", "forward") in table
    assert ("r.attn_core", "backward") in table
    assert ("r.norm", "recompute") in table


def test_the_metrics_read_numbers_on_a_trace_with_regions(tmp_path):
    run = a_run(tmp_path, REGIONS)
    table = op_scopes.table(op_scopes.of_run(run), "jit__step")
    assert run["_op_scopes"] is op_scopes.of_run(run)       # read once
    read = {name: metric(name)(run) for name in NEW_METRICS}
    # (the engine's model has no mixer and no experts: 0 ms of either; the
    # recorded training step is not called `jit__step`, so the training
    # metrics read the engine's step here, passes and all)
    assert all(value is not None for value in read.values())
    assert read["region.ffn_ms.batch"] == pytest.approx(
        table[("r.ffn", "plain")])
    assert read["region.mixer_ms.batch"] == 0
    assert read["region.head_ms.batch"] == pytest.approx(
        table[("r.head", "plain")] + op_scopes.table(
            run["_op_scopes"], "jit__next")[("r.sample", "plain")])
    assert 0 < read["region.unscoped_share.batch"] < 100
    parts = sum(read[f"region.{n}_ms.batch"] for n in (
        "norm", "attn_in", "attn_core", "attn_out", "ffn"))
    rest = sum(ms for (region, _), ms in table.items()
               if region in ("r.embed", "r.cache", op_scopes.UNSCOPED))
    assert parts + table[("r.head", "plain")] + rest == pytest.approx(
        sum(table.values()))
    assert read["train.optimizer_ms"] == pytest.approx(sum(table.values()))
    assert read["train.backward_ms"] == 0


def test_the_tool_prints_a_table_a_program():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "tools",
                                      "regions.py"), REGIONS, "--ops"],
        capture_output=True, text=True, check=True).stdout
    assert "== jit__step:" in out and "== jit__train_step:" in out
    assert "largest operations under no region" in out
    assert "largest under r.ffn:" in out
    step = out.split("== jit__step:")[1].split("== ")[0]
    assert "r.ffn" in step and "plain" in step


def test_the_tool_says_so_where_there_are_no_regions():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "tools",
                                      "regions.py"), BEFORE,
         "--program", "jit__step"],
        capture_output=True, text=True, check=True).stdout
    assert "no operation carries a region" in out
