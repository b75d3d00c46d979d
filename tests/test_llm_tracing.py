"""The serving engine's own measurement (PR 26): spans in the flight
recorder with their parents, one trace id per request, counters against
hand-computed values on a fixed schedule, nothing recorded and nothing
changed with RAY_TPU_TRACE=0, a slow step kept with its phases, and the
program names the benchmark's `step.decode_ms.*` / `step.prefill_ms.chat`
read. CPU, in-process, no cluster.
"""
import os
import subprocess
import sys
import time

import pytest

import jax
import jax.numpy as jnp

from llm_streams import read_stream
from ray_tpu._private import tracing_plane as tp
from ray_tpu._private.config import CONFIG
from ray_tpu.models.config import tiny
from ray_tpu.models.transformer import Transformer
from ray_tpu.serve.llm import engine as engine_mod
from ray_tpu.serve.llm import spans as sp
from ray_tpu.serve.llm.engine import EngineCore, LLMEngine

CORE_SPANS = {sp.STEP, sp.PREFILL, sp.TABLES, sp.DISPATCH, sp.FETCH,
              sp.EMIT}
REQUEST_SPANS = {sp.REQ_QUEUE, sp.REQ_PREFILL, sp.REQ_DECODE}
# table B of ISSUE 26, whole but for the per-frame `stream.encode` and
# `stream.send`, which cost the batch cell more than its bound (PERF.md)
ALL_SPANS = CORE_SPANS | REQUEST_SPANS | {
    sp.WAIT, sp.INGEST, sp.PUBLISH, sp.YIELD,
    sp.SUBMIT}


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny()
    return cfg, Transformer(cfg).init(jax.random.PRNGKey(0))


@pytest.fixture
def recorder():
    """The flight recorder on, at its default size and empty."""
    saved = {k: os.environ.pop(k, None)
             for k in ("RAY_TPU_TRACE", "RAY_TPU_TRACE_RING")}
    CONFIG.reload()
    tp.recorder().clear()
    yield tp.recorder()
    os.environ.pop("RAY_TPU_TRACE", None)       # a test may have set it
    os.environ.update({k: v for k, v in saved.items() if v is not None})
    CONFIG.reload()


def _core(tiny_model, **kw):
    cfg, params = tiny_model
    return EngineCore(cfg, params, **{"num_pages": 32, "page_size": 8,
                                      "max_batch": 2, **kw})


def _run(core, max_steps=50):
    events = []
    for _ in range(max_steps):
        if not core.has_work:
            break
        events.extend(core.step())
    return events


def _mine(rec, names=ALL_SPANS):
    # (trace_id, span_id, parent_span, kind, name, t0, t1, extra)
    return [e for e in rec.snapshot() if e[4] in names]


def test_core_spans_and_parents(tiny_model, recorder):
    core = _core(tiny_model)
    core.submit([3, 17, 91, 254, 8], max_tokens=3, rid="a")
    _run(core)
    evs = _mine(recorder)
    assert {e[4] for e in evs} == CORE_SPANS | REQUEST_SPANS
    assert {e[3] for e in evs} == {"llm"}
    steps = {e[1]: e for e in evs if e[4] == sp.STEP}
    # step 1 admits, dispatches the first decode step and reads the
    # prefill's token; step 2 dispatches the last one and reads the
    # first one's; step 3 has nothing to dispatch and reads the last
    assert len(steps) == 3
    for e in steps.values():
        assert e[2] == 0                       # a root, a trace each
        assert e[7]["t_mono_ns"] <= e[5]       # stamped before it opens
    assert sorted(e[7]["step"] for e in steps.values()) == [1, 2, 3]
    number = {sid: e[7]["step"] for sid, e in steps.items()}
    inside = {n: [e[4] for e in evs if e[2] == sid and e[4] in CORE_SPANS]
              for sid, n in number.items()}
    # (sorted by start) a step's tokens are fetched after the next step
    # is dispatched, and a call with nothing to dispatch only reads
    assert inside == {
        1: [sp.PREFILL, sp.TABLES, sp.DISPATCH, sp.FETCH, sp.EMIT],
        2: [sp.TABLES, sp.DISPATCH, sp.FETCH, sp.EMIT],
        3: [sp.FETCH, sp.EMIT]}
    for e in evs:
        if e[4] in CORE_SPANS - {sp.STEP}:
            parent = steps[e[2]]               # KeyError = wrong parent
            assert e[0] == parent[0]           # the step's trace
            assert parent[5] <= e[5] <= e[6] <= parent[6]
    pre = [e for e in evs if e[4] == sp.PREFILL]
    assert [e[7] for e in pre] == [
        {"rid": "a", "tokens": 5, "bucket": 16, "new_program": 1}]
    disp = [e[7] for e in evs if e[4] == sp.DISPATCH]
    # the einsum (the tiny model's heads are no shape the kernel tiles)
    # gathers every lane's whole table, whatever the lanes hold
    read = 2 * core.max_pages_per_seq * 8
    # and multiplies all of it, in no blocks and no copies of a walk
    assert disp == [
        {"lanes": 1, "live_positions": 6, "read_positions": read,
         "walk_blocks": 0, "walk_copies": 0, "attended_positions": read,
         "walk_first_blocks_hidden": 0},
        {"lanes": 1, "live_positions": 7, "read_positions": read,
         "walk_blocks": 0, "walk_copies": 0, "attended_positions": read,
         "walk_first_blocks_hidden": 0}]


def test_read_positions_under_the_kernel_are_the_pages_held(tiny_model,
                                                            recorder):
    """What the counts mean where the decode step holds the paged kernel
    (the test says so in the engine's stead: the compiled step here is
    the einsum still): each lane's live pages, whole."""
    from ray_tpu.ops.paged_attention import KERNEL_PAGED_DECODE
    core = _core(tiny_model)
    core._attention = KERNEL_PAGED_DECODE
    core.submit(list(range(1, 6)), max_tokens=3, rid="a")
    core.submit(list(range(1, 21)), max_tokens=2, rid="b")
    _run(core)
    disp = [e[7] for e in _mine(recorder) if e[4] == sp.DISPATCH]
    # 8-position pages: a holds 6 then 7 positions (1 page), b 21 (3);
    # a block is the lane's whole table here, a block a lane, multiplied
    # over a piece of 128 positions (16 pages: all the table has)
    assert core._walk_block == core.max_pages_per_seq <= 16
    piece = core.max_pages_per_seq * 8
    assert disp == [
        {"lanes": 2, "live_positions": 6 + 21, "read_positions": 8 + 24,
         "walk_blocks": 2, "walk_copies": 1 + 3,    # a copy a page
         "attended_positions": 2 * piece,
         "walk_first_blocks_hidden": 1},        # b's, behind a's
        {"lanes": 1, "live_positions": 7, "read_positions": 8,
         "walk_blocks": 1, "walk_copies": 1, "attended_positions": piece,
         "walk_first_blocks_hidden": 0}]        # a call's first lane waits
    st = core.stats()
    assert st["kv_walk_blocks"] == 3
    assert st["kv_walk_copies"] == 5
    assert st["kv_walk_first_blocks_hidden"] == 1
    assert st["kv_positions_attended"] == 3 * piece
    assert st["kv_positions_read"] == 8 + 24 + 8
    assert st["kv_positions_live"] == 6 + 21 + 7
    assert st["decode_kernel_steps"] == st["decode_steps"] == 2
    assert core.device_stats()["decode_attention"] == "paged_decode_attn"


def test_full_lanes_hide_every_first_block_but_one_a_step(tiny_model,
                                                         recorder):
    """Where the decode step holds the paged kernel, a dispatch's lanes
    but one find their first block started by the lane before them: on
    full lanes `walk_first_blocks_hidden` reads `lanes - 1` a step, and
    `kv_walk_first_blocks_hidden` over `decode_lane_steps` (lanes - 1) /
    lanes; `engine_stats()` carries both."""
    from ray_tpu.ops.paged_attention import KERNEL_PAGED_DECODE
    lanes = 4
    core = _core(tiny_model, num_pages=64, max_batch=lanes)
    core._attention = KERNEL_PAGED_DECODE
    for i in range(lanes):
        core.submit([7, 8, 9 + i], max_tokens=5, rid=f"r{i}")
    _run(core)
    disp = [e[7] for e in _mine(recorder) if e[4] == sp.DISPATCH]
    assert [d["lanes"] for d in disp] == [lanes] * 4
    assert [d["walk_first_blocks_hidden"] for d in disp] == [lanes - 1] * 4
    st = core.stats()
    assert st["decode_lane_steps"] == 4 * lanes
    assert st["kv_walk_first_blocks_hidden"] == 4 * (lanes - 1)
    eng = LLMEngine(model="tiny", num_pages=32, page_size=8, max_batch=2,
                    seed=0)
    try:        # the einsum here: nothing is walked, the count is there
        assert len(read_stream(eng.generate([4, 5], max_tokens=2))[0]) == 2
        st = eng.engine_stats()
        assert st["decode_lane_steps"] >= 1
        assert st["kv_walk_first_blocks_hidden"] == 0
    finally:
        eng.close()


def test_request_spans_share_one_trace(tiny_model, recorder):
    core = _core(tiny_model)
    core.submit([1, 2, 3], max_tokens=4, rid="a")
    core.submit([4, 5, 6, 7], max_tokens=2, rid="b")
    _run(core)
    evs = _mine(recorder)
    step_sids = {e[1] for e in evs if e[4] == sp.STEP}
    for rid in ("a", "b"):
        mine = [e for e in evs if e[4] in REQUEST_SPANS
                and e[7]["rid"] == rid]
        assert [e[4] for e in mine] == [sp.REQ_QUEUE, sp.REQ_PREFILL,
                                        sp.REQ_DECODE]
        assert len({e[0] for e in mine}) == 1      # one trace id
        assert all(e[2] in step_sids for e in mine)
        q, p, d = mine
        assert q[5] <= q[6] == p[5] <= p[6] == d[5] <= d[6]
    traces = {e[0] for e in evs if e[4] in REQUEST_SPANS}
    assert len(traces) == 2                        # one per request
    assert not traces & {e[0] for e in evs if e[4] == sp.STEP}


def test_counters_on_a_fixed_schedule(tiny_model):
    """Two prompts, 5 tokens (bucket 16) and 20 (bucket 32), 3 and 2
    tokens out, two lanes. Step 1 prefills both, dispatches both lanes'
    decode step (b's last: its lane and pages come free) and reads the
    prefills' tokens. Step 2 dispatches a alone and reads the first
    decode step: b is done. Step 3 reads a's last token."""
    core = _core(tiny_model)
    core.submit(list(range(1, 6)), max_tokens=3, rid="a")
    core.submit(list(range(1, 21)), max_tokens=2, rid="b")
    events = _run(core)
    assert [(e["rid"], e["seq"]) for e in events] == [
        ("a", 0), ("b", 0), ("a", 1), ("b", 1), ("a", 2)]
    read = 2 * core.max_pages_per_seq * 8
    st = core.stats()
    assert {k: st[k] for k in (
        "steps", "admitted", "finished", "tokens", "prefill_tokens",
        "prefill_padded_tokens", "prefill_programs", "decode_steps",
        "decode_kernel_steps", "decode_lane_steps", "kv_positions_live",
        "kv_positions_read")} == {
        "steps": 3, "admitted": 2, "finished": 2, "tokens": 5,
        "prefill_tokens": 25, "prefill_padded_tokens": 48,
        "prefill_programs": 2, "decode_steps": 2, "decode_lane_steps": 3,
        # the einsum ran both (tiny heads): the old constant a step
        "decode_kernel_steps": 0,
        # step 1: a holds 5 + 1, b 20 + 1; step 2: a holds 5 + 2
        "kv_positions_live": 6 + 21 + 7, "kv_positions_read": 2 * read}
    # the second dispatch went out before the first was read; the last
    # was read with nothing behind it; nobody's token was dropped
    assert (st["decode_steps_ahead"], st["pipeline_flushes"],
            st["discarded_lane_steps"]) == (1, 1, 0)
    assert core.device_stats()["decode_attention"] == "einsum"
    # (a step that compiles may well take a second: slow_steps keeps it)
    assert all(s["step"] == 1 for s in st["slow_steps"])
    # a third request in a bucket already built: no new program
    core.submit(list(range(1, 10)), max_tokens=1, rid="c")
    assert [(e["rid"], e["seq"], e["reason"]) for e in _run(core)] == [
        ("c", 0, "length")]         # one call: a prefill and its token
    assert core.counters["prefill_programs"] == 2
    assert core.counters["prefill_padded_tokens"] == 48 + 16
    assert core.counters["decode_steps"] == 2       # it took no lane-step
    assert core.counters["pipeline_flushes"] == 1   # nothing was in flight


def test_trace_off_records_nothing_and_changes_no_token(tiny_model,
                                                         recorder):
    def tokens():
        core = _core(tiny_model)
        core.submit([9, 8, 7, 6], max_tokens=4, rid="a")
        core.submit([5, 4], max_tokens=3, rid="b")
        return ([(e["rid"], e["token"]) for e in _run(core)],
                dict(core.counters))

    on = tokens()
    assert _mine(recorder)
    os.environ["RAY_TPU_TRACE"] = "0"
    CONFIG.reload()
    assert not tp.enabled()
    off = tokens()
    assert tp.recorder().watermark() == 0 and not _mine(tp.recorder())
    assert off == on


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 0.001
        return self.t


def test_slow_step_is_kept_with_its_phases(tiny_model, monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(engine_mod, "_clock", clock)
    core = _core(tiny_model)
    core.submit([1, 2, 3], max_tokens=3, rid="a")
    core.step()                                 # quick: not kept
    assert not core.slow_steps

    fetch = core._np.asarray

    class SlowNumpy:
        """The device's answer comes 1.5 s late, on the patched clock."""
        def __getattr__(self, name):
            return getattr(core_np, name)

        def asarray(self, x, *a, **k):
            if not isinstance(x, core_np.ndarray):
                clock.t += 1.5
            return fetch(x, *a, **k)

    core_np = core._np
    core._np = SlowNumpy()
    core.step()         # dispatches the last step, waits for the first
    core._np = core_np
    core.step()
    assert not core.has_work
    assert [s["step"] for s in core.slow_steps] == [2]
    slow = core.stats()["slow_steps"][0]
    assert slow["lanes"] == 1 and slow["t_mono_ns"] > 0
    assert 1.5 < slow["wall_s"] < 1.6
    assert set(slow["phases"]) == {sp.TABLES, sp.DISPATCH, sp.FETCH,
                                   sp.EMIT}
    assert 1.5 < slow["phases"][sp.FETCH] < 1.51
    assert sum(slow["phases"].values()) <= slow["wall_s"]
    assert engine_mod.SLOW_STEP_S == 1.0
    assert core.slow_steps.maxlen == 16


def test_program_names_the_benchmark_reads(tiny_model):
    """`step.decode_ms.*` and `step.prefill_ms.chat` find the programs
    in a device trace as `jit__step` and `jit__pre`."""
    core = _core(tiny_model)
    B, P = core.max_batch, core.max_pages_per_seq
    dec = core._decode_fn.lower(
        core.params, core._cache, jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), jnp.int32), jnp.zeros((B, P), jnp.int32),
        jnp.zeros((B,), bool))
    assert "module @jit__step" in dec.as_text()
    pre = core._prefill_fn(16).lower(
        core.params, jnp.zeros((16,), jnp.int32), jnp.int32(3),
        jnp.zeros((P,), jnp.int32), core._cache)
    assert "module @jit__pre" in pre.as_text()


def test_llm_engine_emits_every_span_of_the_table(tiny_model, recorder,
                                                  monkeypatch):
    # `stream.publish` is written where a frame is: a real pause between
    # steps, so the subscriber is let in before the generation is over
    # (at 0 the step thread can re-take its lock through all four steps)
    monkeypatch.setenv("RAY_TPU_LLM_STEP_DELAY_S", "0.02")
    CONFIG.reload()
    eng = LLMEngine(model="tiny", num_pages=32, page_size=8, max_batch=2,
                    seed=0)
    try:
        time.sleep(0.12)                # idle: the step thread waits
        acc = eng.generate([4, 5, 6], max_tokens=4, rid="s")
        assert len(read_stream(acc)[0]) == 4
        st = eng.engine_stats()
        assert st["decode_steps"] >= 1
        assert isinstance(st["slow_steps"], list)
    finally:
        eng.close()
    evs = _mine(recorder)
    assert {e[4] for e in evs} == ALL_SPANS
    by_sid = {e[1]: e for e in evs}
    parent_name = {e[4]: by_sid[e[2]][4] for e in evs
                   if e[2] in by_sid and e[4] not in REQUEST_SPANS}
    assert parent_name == {
        sp.PREFILL: sp.STEP, sp.TABLES: sp.STEP, sp.DISPATCH: sp.STEP,
        sp.FETCH: sp.STEP, sp.EMIT: sp.STEP, sp.PUBLISH: sp.INGEST}
    for name in (sp.WAIT, sp.STEP, sp.INGEST, sp.YIELD, sp.SUBMIT):
        assert all(e[2] == 0 for e in evs if e[4] == name), name
    sub = [e for e in evs if e[4] == sp.SUBMIT]
    assert [e[7] for e in sub] == [{"rid": "s"}]
    # frames written, one a connection, and the requests' records in them
    assert all(e[7]["records"] >= e[7]["frames"] >= 1
               for e in evs if e[4] == sp.PUBLISH)


def test_step_histogram_on_the_metrics_plane(tiny_model):
    from ray_tpu._private import metrics_plane
    from ray_tpu.util.metrics import DEFAULT_REGISTRY
    if not metrics_plane.enabled():
        pytest.skip("metrics plane off")
    eng = LLMEngine(model="tiny", num_pages=32, page_size=8, max_batch=2,
                    seed=0)
    try:
        assert len(read_stream(
            eng.generate([1, 2, 3], max_tokens=3, rid="h"))[0]) == 3
    finally:
        eng.close()
    assert "ray_tpu_llm_step_s" in DEFAULT_REGISTRY.prometheus_text()


def test_engine_module_imports_no_jax():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, ray_tpu.serve.llm.engine, ray_tpu.serve.llm.stream;"
         "print('jax' in sys.modules)"],
        capture_output=True, text=True, timeout=60,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.stdout.strip() == "False", out.stderr[-400:]


# ------------------------------------------- the second architecture
@pytest.fixture(scope="module")
def tiny_mla():
    from ray_tpu.models.mla_moe import MLAMoE, tiny_mla_moe
    cfg = tiny_mla_moe()
    return cfg, MLAMoE(cfg).init(jax.random.PRNGKey(0))


def test_program_names_are_the_same_for_the_second_architecture(tiny_mla):
    """`jit__step` / `jit__pre` whatever the model: the engine jits its own
    two functions, and the model is what they call."""
    core = _core(tiny_mla)
    B, P = core.max_batch, core.max_pages_per_seq
    dec = core._decode_fn.lower(
        core.params, core._cache, jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), jnp.int32), jnp.zeros((B, P), jnp.int32),
        jnp.zeros((B,), bool))
    assert "module @jit__step" in dec.as_text()
    pre = core._prefill_fn(16).lower(
        core.params, jnp.zeros((16,), jnp.int32), jnp.int32(3),
        jnp.zeros((P,), jnp.int32), core._cache)
    assert "module @jit__pre" in pre.as_text()


def test_kernel_and_counter_names_the_expert_metrics_read():
    """`kernel.mla_decode_roofline.batch32`, `kernel.moe_gmm_roofline.
    batch32` and `step.moe_ms.batch32` find device events by these names
    (`benchmarks/harness/decode_events.py`), and the `moe.*` metrics the
    attributes of `engine.emit`."""
    from ray_tpu.ops import grouped_matmul, paged_attention
    assert paged_attention.KERNEL_MLA_PAGED_DECODE == "mla_paged_decode_attn"
    assert grouped_matmul.KERNEL_GMM == "moe_gmm"
    assert sp.EMIT == "engine.emit" and sp.DISPATCH == \
        "engine.decode_dispatch"
    # (that a compiled program carries them: tests/test_kernel_names_aot.py)


def test_expert_counts_ride_the_step_s_emit_span(tiny_mla, recorder):
    cfg, _ = tiny_mla
    core = _core(tiny_mla)
    core.submit([3, 17, 91, 254, 8], max_tokens=3, rid="a")
    core.submit([4, 5, 6], max_tokens=2, rid="b")
    _run(core)
    evs = _mine(recorder)
    assert {e[4] for e in evs} == CORE_SPANS | REQUEST_SPANS
    emits = [e[7] for e in evs if e[4] == sp.EMIT]
    lanes = [e[7]["lanes"] for e in evs if e[4] == sp.DISPATCH]
    assert lanes == [2, 1]
    # the first call emits the prefills' tokens: no decode step's, so no
    # count; then each call emits the step dispatched by the call before
    assert not emits[0] and len(emits) == 3
    emits = emits[1:]
    per_lane = cfg.num_experts_per_tok * cfg.n_moe_layers
    assert [e["moe_pairs"] for e in emits] == [n * per_lane for n in lanes]
    for e, n in zip(emits, lanes):
        assert cfg.n_moe_layers <= e["moe_load_max"] <= e["moe_pairs"]
        assert e["moe_load_max"] <= e["moe_experts_touched"] * n
        assert e["moe_experts_touched"] <= e["moe_pairs"]
    st = core.stats()
    assert st["moe_pairs"] == sum(e["moe_pairs"] for e in emits)
    assert st["moe_experts_touched"] == sum(
        e["moe_experts_touched"] for e in emits)
    # latent rows: the same meaning of live and read positions
    assert st["kv_positions_live"] == (6 + 4) + 7
    assert core.device_stats()["decode_attention"] == "einsum"


def test_a_dense_step_s_emit_span_carries_no_expert_count(tiny_model,
                                                          recorder):
    core = _core(tiny_model)
    core.submit([1, 2, 3], max_tokens=2, rid="a")
    _run(core)
    emits = [e[7] for e in _mine(recorder) if e[4] == sp.EMIT]
    # the prefill's token, then the one decode step's
    assert len(emits) == 2 and not any(emits)
    assert "moe_pairs" not in core.stats()


def test_each_emit_span_carries_the_counts_of_the_step_it_emits(tiny_mla,
                                                                recorder):
    """Three lanes that end one after the other: decode step k is
    dispatched in call k with 3, 2, 1 lanes, its tokens are emitted in
    call k + 1, and that call's `engine.emit` holds step k's counts (the
    cache they were counted in has been donated to step k + 1 by then)."""
    cfg, _ = tiny_mla
    core = _core(tiny_mla, max_batch=3)
    for rid, n in (("a", 4), ("b", 3), ("c", 2)):
        core.submit([7, 8, 9], max_tokens=n, rid=rid)
    _run(core)
    evs = _mine(recorder)
    call = {e[1]: e[7]["step"] for e in evs if e[4] == sp.STEP}
    lanes = {call[e[2]]: e[7]["lanes"] for e in evs if e[4] == sp.DISPATCH}
    assert lanes == {1: 3, 2: 2, 3: 1}
    pairs = {call[e[2]]: (e[7] or {}).get("moe_pairs")
             for e in evs if e[4] == sp.EMIT}
    per_lane = cfg.num_experts_per_tok * cfg.n_moe_layers
    assert pairs == {1: None, 2: 3 * per_lane, 3: 2 * per_lane,
                     4: 1 * per_lane}
    assert core.stats()["moe_pairs"] == 6 * per_lane
