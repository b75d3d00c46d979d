"""The engine's account of its own start (PR 39): the table of the programs
it built, with JAX's phases inside each build; the `engine.setup*`,
`engine.build_program` and `engine.lock_wait` spans; the record that
outlives the ring (`engine_stats()`'s `setup` and `programs`, the series on
the metrics plane); and the six metric files that read them. CPU,
in-process, no cluster, the tiny models.
"""
import importlib.util
import os
import time

import pytest

import jax

from llm_streams import read_stream
from test_llm_tracing import (_core, _run, recorder, tiny_mla,    # noqa: F401
                              tiny_model)
from ray_tpu._private import metrics_plane
from ray_tpu._private import tracing_plane as tp
from ray_tpu._private.config import CONFIG
from ray_tpu.serve.llm import spans as sp
from ray_tpu.serve.llm.engine import LLMEngine
from ray_tpu.util.metrics import (DEFAULT_REGISTRY, Counter,
                                  MetricsRegistry)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SETUP_SPANS = {sp.SETUP, sp.SETUP_MODEL, sp.SETUP_WEIGHTS, sp.SETUP_CACHE,
               sp.SETUP_PROGRAMS}
SERIES = ("ray_tpu_llm_setup_s", "ray_tpu_llm_program_build_s",
          "ray_tpu_llm_program_builds")
SETUP_METRICS = ("setup.engine_init_s", "setup.program_build_s",
                 "setup.trace_lower_s", "setup.compile_or_load_s",
                 "setup.cache_miss_programs")


def _spans(rec, *names):
    # (trace_id, span_id, parent_span, kind, name, t0, t1, extra)
    return [e for e in rec.snapshot() if e[4] in names]


def _keys(core):
    return [(r["program"], r["bucket"], r["rebuild"])
            for r in core.record.programs]


def _fixed_schedule(core):
    """Prompts of 5 tokens (bucket 16) and 20 (bucket 32), two lanes."""
    core.submit(list(range(1, 6)), max_tokens=3, rid="a")
    core.submit(list(range(1, 21)), max_tokens=2, rid="b")
    return _run(core)


def _check_first_builds(core):
    rows = core.stats()["programs"]
    assert sorted(_keys(core)) == sorted([
        ("_pre", 16, False), ("_pre", 32, False), ("_place", 0, False),
        ("_step", 0, False), ("_next", 0, False)])
    for r in rows:
        assert r["step"] == 1 and r["t_mono_ns"] > 0
        assert min(r["trace_s"], r["lower_s"], r["compile_s"]) > 0, r
        assert r["trace_s"] + r["lower_s"] + r["compile_s"] <= r["wall_s"]
        # the tests' processes keep no persistent cache: XLA compiled it
        assert r["cache_hit"] is False and "saved_s" not in r
    # in the order they were dispatched, none inside another
    for a, b in zip(rows, rows[1:]):
        assert a["t_mono_ns"] + a["wall_s"] * 1e9 <= b["t_mono_ns"] + 1e3
    assert core.stats()["prefill_programs"] == 2 == core.record.count("_pre")


def test_the_table_holds_every_program_once(tiny_model, recorder):
    core = _core(tiny_model)
    _fixed_schedule(core)
    _check_first_builds(core)
    builds = _spans(recorder, sp.BUILD)
    assert [(e[7]["program"], e[7]["bucket"], e[7]["rebuild"])
            for e in builds] == [(p, b, int(r)) for p, b, r in _keys(core)]
    for e, row in zip(builds, core.record.programs):
        assert e[3] == "llm"
        # what is known when the build ends rides the recorder
        assert {k: e[7][k] for k in ("cache_hit", "trace_s", "lower_s",
                                     "compile_s")} == {
            k: row[k] for k in ("cache_hit", "trace_s", "lower_s",
                                "compile_s")}
    # each inside the span of the dispatch that built it
    by_sid = {e[1]: e for e in recorder.snapshot()}
    parents = [(e[7]["program"], by_sid[e[2]][4]) for e in builds]
    assert parents == [("_pre", sp.PREFILL), ("_place", sp.PREFILL),
                       ("_pre", sp.PREFILL), ("_step", sp.DISPATCH),
                       ("_next", sp.DISPATCH)]
    for e in builds:
        parent = by_sid[e[2]]
        assert parent[5] <= e[5] <= e[6] <= parent[6]


def test_a_built_bucket_adds_nothing_and_a_new_one_adds_one(tiny_model,
                                                            recorder):
    core = _core(tiny_model)
    _fixed_schedule(core)
    before = len(_spans(recorder, sp.BUILD))
    core.submit(list(range(1, 10)), max_tokens=2, rid="c")     # bucket 16
    _run(core)
    assert len(core.record.programs) == 5
    assert len(_spans(recorder, sp.BUILD)) == before
    core.submit(list(range(1, 41)), max_tokens=2, rid="d")     # bucket 64
    _run(core)
    assert _keys(core)[5:] == [("_pre", 64, False)]
    assert core.counters["prefill_programs"] == 3
    new = _spans(recorder, sp.BUILD)[before:]
    assert [e[7]["bucket"] for e in new] == [64]
    prefill = {e[1]: e for e in _spans(recorder, sp.PREFILL)}[new[0][2]]
    assert prefill[7]["rid"] == "d" and prefill[7]["new_program"] == 1
    assert core.record.programs[5]["step"] == prefill_step(recorder, prefill)


def prefill_step(rec, prefill):
    return {e[1]: e[7]["step"] for e in _spans(rec, sp.STEP)}[prefill[2]]


def test_a_cleared_cache_is_recorded_as_a_rebuild(tiny_model, recorder,
                                                  monkeypatch):
    from ray_tpu.serve.llm import engine as engine_mod
    core = _core(tiny_model)
    core.submit([1, 2, 3], max_tokens=4, rid="a")
    core.step()                 # prefill, the first decode step
    assert len(core.record.programs) == 4
    jax.clear_caches()
    monkeypatch.setattr(engine_mod, "SLOW_STEP_S", 0.0)  # keep every step
    core.step()                 # decode only: no prefill, so no `_pre`
    assert _keys(core)[4:] == [("_step", 0, True), ("_next", 0, True)]
    assert all(r["step"] == 2 for r in core.record.programs[4:])
    # "which step recompiled", from inside: the step's own entry says so
    slow = core.slow_steps[-1]
    assert slow["step"] == 2
    assert [(b["program"], b["rebuild"]) for b in slow["builds"]] == [
        ("_step", True), ("_next", True)]
    assert [e[7]["rebuild"] for e in _spans(recorder, sp.BUILD)[4:]] == [1, 1]
    _run(core)
    assert len(core.record.programs) == 6       # built again, once
    assert core.record.count("_step") == 1      # the rebuild apart


def test_engine_setup_spans_stats_and_series(recorder):
    if not metrics_plane.enabled():
        pytest.skip("metrics plane off")
    built = _series_total("ray_tpu_llm_program_builds")
    eng = LLMEngine(model="tiny", num_pages=32, page_size=8, max_batch=2,
                    seed=0)
    try:
        assert len(read_stream(
            eng.generate([4, 5, 6], max_tokens=3, rid="s"))[0]) == 3
        st = eng.engine_stats()
    finally:
        eng.close()
    evs = _spans(recorder, *SETUP_SPANS)
    assert {e[4] for e in evs} == SETUP_SPANS
    whole = [e for e in evs if e[4] == sp.SETUP]
    assert len(whole) == 1 and whole[0][2] == 0
    children = sorted((e for e in evs if e[4] != sp.SETUP),
                      key=lambda e: e[5])
    # the engine builds the model object to shape its weights, the core
    # again for itself
    assert [e[4] for e in children] == [
        sp.SETUP_MODEL, sp.SETUP_WEIGHTS, sp.SETUP_MODEL, sp.SETUP_CACHE,
        sp.SETUP_PROGRAMS]
    for a, b in zip(children, children[1:]):
        assert a[6] <= b[5]                         # none overlaps the next
    assert all(e[2] == whole[0][1] for e in children)
    assert sum(e[6] - e[5] for e in children) <= whole[0][6] - whole[0][5]
    attrs = {e[4]: e[7] for e in children}
    assert attrs[sp.SETUP_CACHE]["num_pages"] == 32
    assert attrs[sp.SETUP_CACHE]["fixed_pages"] == 0
    assert attrs[sp.SETUP_CACHE]["bytes"] > 0
    assert attrs[sp.SETUP_WEIGHTS]["bytes"] > 0
    # the record that outlives the ring
    assert set(st["setup"]) == SETUP_SPANS
    parts = sum(v for k, v in st["setup"].items() if k != sp.SETUP)
    assert 0 < parts <= st["setup"][sp.SETUP]
    programs = [(r["program"], r["bucket"], r["step"]) for r in
                st["programs"]]
    assert programs[0] == ("init", 0, 0)
    assert sorted(p for p, _, _ in programs) == [
        "_next", "_place", "_pre", "_step", "init"]
    init = _spans(recorder, sp.BUILD)[0]
    weights = next(e for e in children if e[4] == sp.SETUP_WEIGHTS)
    assert init[7]["program"] == "init" and init[2] == weights[1]
    assert st["lock_waits"] >= 0 and st["lock_wait_s"] >= 0.0
    # and the series an operator scrapes
    text = DEFAULT_REGISTRY.prometheus_text()
    for name in SERIES:
        assert f"# TYPE {name} counter" in text
    assert 'ray_tpu_llm_setup_s{phase="engine.setup"}' in text
    assert 'ray_tpu_llm_program_build_s{program="_step",phase="trace"}' \
        in text
    assert 'ray_tpu_llm_program_builds{cache="miss",rebuild="0"}' in text
    assert _series_total("ray_tpu_llm_program_builds") == built + 5


def _series_total(name):
    metric = DEFAULT_REGISTRY.get(name)
    return sum(metric.snapshot()["series"].values()) if metric else 0.0


def test_trace_off_keeps_the_table_and_the_series(tiny_model, recorder):
    def run():
        core = _core(tiny_model)
        tokens = [(e["rid"], e["token"]) for e in _fixed_schedule(core)]
        return tokens, _keys(core), dict(core.counters)

    on = run()
    assert _spans(recorder, sp.BUILD, *SETUP_SPANS)
    os.environ["RAY_TPU_TRACE"] = "0"
    CONFIG.reload()
    assert not tp.enabled()
    built = _series_total("ray_tpu_llm_program_builds")
    off = run()
    assert tp.recorder().watermark() == 0
    assert off == on and len(off[1]) == 5
    if metrics_plane.enabled():
        assert _series_total("ray_tpu_llm_program_builds") == built + 5


def test_the_second_architecture_writes_the_same_names(tiny_mla, recorder):
    core = _core(tiny_mla)
    _fixed_schedule(core)
    _check_first_builds(core)
    assert set(core.stats()["setup"]) == SETUP_SPANS - {sp.SETUP,
                                                        sp.SETUP_WEIGHTS}
    assert len(_spans(recorder, sp.BUILD)) == 5


def test_two_engines_on_one_thread_keep_their_own_tables(tiny_model):
    a, b = _core(tiny_model), _core(tiny_model)
    a.submit([1, 2, 3], max_tokens=2, rid="a")
    b.submit(list(range(1, 30)), max_tokens=2, rid="b")
    for _ in range(4):
        a.step()
        b.step()
    assert sorted(r["bucket"] for r in a.record.programs) == [0, 0, 0, 16]
    assert sorted(r["bucket"] for r in b.record.programs) == [0, 0, 0, 32]
    # outside a step the thread is nobody's: this build is in no table
    a._decode_fn.lower(a.params, a._cache, a._tokens,
                       *[jax.numpy.zeros(s, d) for s, d in (
                           ((2,), "int32"), ((2, a.max_pages_per_seq),
                                             "int32"), ((2,), bool))])
    assert len(a.record.programs) == len(b.record.programs) == 4
    assert a.record.open is None and b.record.open is None


def test_the_step_thread_s_wait_for_its_lock_is_a_span(recorder):
    eng = LLMEngine(model="tiny", num_pages=32, page_size=8, max_batch=2,
                    seed=0)
    try:
        time.sleep(0.15)        # idle and uncontended: no wait is written
        assert not _spans(recorder, sp.LOCK_WAIT)
        assert eng.engine_stats()["lock_waits"] == 0
        # a caller holds the lock while the step thread comes for it
        with eng._lock:
            time.sleep(0.2)
        deadline = time.monotonic() + 5.0
        while not eng._lock_waits and time.monotonic() < deadline:
            time.sleep(0.01)
        st = eng.engine_stats()
    finally:
        eng.close()
    assert st["lock_waits"] >= 1
    assert 0.05 < st["lock_wait_s"] < 5.0
    waits = _spans(recorder, sp.LOCK_WAIT)
    assert len(waits) == st["lock_waits"]
    assert all(e[2] == 0 and e[3] == "llm" for e in waits)
    assert max(e[6] - e[5] for e in waits) > 0.05e9


# ------------------------------------------------ the metric files
def metric(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(ROOT, "benchmarks", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture()
def filled():
    """A registry as an engine leaves it: `init` and `_step` missed the
    cache, two buckets' `_pre` hit it, `_step` was built again."""
    reg = MetricsRegistry()
    setup = Counter("ray_tpu_llm_setup_s", tag_keys=("phase",),
                    registry=reg)
    for phase, s in ((sp.SETUP, 7.5), (sp.SETUP_MODEL, 0.5),
                     (sp.SETUP_WEIGHTS, 4.0), (sp.SETUP_CACHE, 1.0),
                     (sp.SETUP_PROGRAMS, 0.25)):
        setup.inc(s, {"phase": phase})
    build = Counter("ray_tpu_llm_program_build_s",
                    tag_keys=("program", "phase"), registry=reg)
    for program, trace, lower, compile_, rest in (
            ("init", 0.5, 0.25, 2.0, 0.25), ("_step", 2.0, 1.0, 8.0, 0.5),
            ("_pre", 3.0, 1.5, 0.5, 0.25)):
        for phase, s in (("trace", trace), ("lower", lower),
                         ("compile", compile_), ("rest", rest)):
            build.inc(s, {"program": program, "phase": phase})
    builds = Counter("ray_tpu_llm_program_builds",
                     tag_keys=("cache", "rebuild"), registry=reg)
    builds.inc(2, {"cache": "miss", "rebuild": "0"})
    builds.inc(2, {"cache": "hit", "rebuild": "0"})
    builds.inc(1, {"cache": "miss", "rebuild": "1"})
    return {"_metrics": reg.collect()}


@pytest.mark.parametrize("name,want", [
    ("setup.engine_init_s", 7.5),
    ("setup.program_build_s", 19.75),
    ("setup.trace_lower_s", 8.25),
    ("setup.compile_or_load_s", 10.5),
    ("setup.cache_miss_programs", 3.0)])
def test_a_setup_metric_reads_its_series(filled, name, want):
    assert metric(name)(filled) == want
    # a program that writes no such series (the parent): off the line
    assert metric(name)({"_metrics": MetricsRegistry().collect()}) is None
    # registered and never written (no engine was made): the same
    empty = MetricsRegistry()
    for series, keys in (("ray_tpu_llm_setup_s", ("phase",)),
                         ("ray_tpu_llm_program_build_s",
                          ("program", "phase")),
                         ("ray_tpu_llm_program_builds",
                          ("cache", "rebuild"))):
        Counter(series, tag_keys=keys, registry=empty)
    assert metric(name)({"_metrics": empty.collect()}) is None


def test_a_warm_run_reads_no_missed_program():
    reg = MetricsRegistry()
    Counter("ray_tpu_llm_program_builds", tag_keys=("cache", "rebuild"),
            registry=reg).inc(9, {"cache": "hit", "rebuild": "0"})
    assert metric("setup.cache_miss_programs")(
        {"_metrics": reg.collect()}) == 0.0


def test_the_setup_metrics_read_this_process_s_registry(tiny_model):
    if not metrics_plane.enabled():
        pytest.skip("metrics plane off")
    _fixed_schedule(_core(tiny_model))
    run = {}
    got = {name: metric(name)(run) for name in SETUP_METRICS}
    assert got["setup.cache_miss_programs"] >= 5
    assert 0 < got["setup.trace_lower_s"] + got["setup.compile_or_load_s"] \
        <= got["setup.program_build_s"]


def test_the_lock_gap_metric_reads_the_span():
    from benchmarks.harness import spans as hspans
    from benchmarks.harness import xplane
    E = xplane.Event
    step = [E(sp.STEP, 0.0, 0.010), E(sp.DISPATCH, 0.001, 0.002),
            E(sp.LOCK_WAIT, 0.010, 0.004), E(sp.STEP, 0.014, 0.010),
            E(sp.DISPATCH, 0.015, 0.002)]
    reading = hspans.Reading(step, {sp.LOCK_WAIT: 0.003, sp.STEP: 0.001},
                             0.004)
    read = metric("engine.gap_lock_ms_per_step")
    assert read({"_spans": reading}) == pytest.approx(1.5)
    # no wait written (an uncontended lock, or the parent's program): 0
    quiet = hspans.Reading(step[:2], {sp.STEP: 0.001}, 0.001)
    assert read({"_spans": quiet}) == 0.0
    assert read({"_spans": None}) is None          # an untraced run


def test_benchmark_json_lists_the_six_where_they_read():
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    serving = [w["name"] for w in bench["workloads"] if ".serve." in
               w["name"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in SETUP_METRICS:
        m = by_name[name]
        assert (m["moves"], m["layer"], m["better"], m["source"]) == (
            "setup_s", "engine", "lower", "program_counter")
        assert m["workloads"] == serving
    lock = by_name["engine.gap_lock_ms_per_step"]
    assert (lock["moves"], lock["source"]) == ("serve_tokens_per_s",
                                               "program_span")
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert lock["workloads"] == moved["workloads"]
