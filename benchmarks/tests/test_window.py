"""The traced window: a span inside the trace, read by one function
(`xplane.traced_window`), with busy time, idle gaps and the breakdown cut
to it. On traces built by hand, on the recorded trace of a device that
never pauses while the profiler starts and stops
(benchmarks/tests/data/record_window_trace.py printed the numbers used
here), and through a traced rehearsal of `run.py` on the CPU."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import xplane
from benchmarks.harness.xplane import WINDOW, Event as E, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BUSY = os.path.join(HERE, "data", "busy_window_v5e.xplane.pb")

LO, HI = 10.0, 16.0                         # the marker: a window of 6 s


def a_trace(ops, lead=0.0, marks=((LO, HI),), host=()):
    """Device 0 running `ops` (start, end) on a clock that leads the host's
    by `lead`; the harness's thread holds `marks`; one program spans each
    operation, enqueued by the host the moment it starts."""
    ops = [E(f"%fusion.{i} = bf16[8]{{0}} fusion()", a - lead, b - a)
           for i, (a, b) in enumerate(ops)]
    modules = [E(f"jit__step({i})", o.start, o.dur, {"run_id": i})
               for i, o in enumerate(ops)]
    enqueues = {i: o.start + lead for i, o in enumerate(ops)}
    threads = {"python3": [E(WINDOW, a, b - a) for a, b in marks],
               "llm-engine-step": list(host)}
    return Trace({0: modules}, {0: ops}, threads, enqueues)


# busy across both ends of the marker, as a device the engine keeps fed:
# 7 ms recorded before the window and 12 ms after it
NEVER_IDLE = [(LO - 0.007, 12.0), (12.0, HI + 0.012)]
ONE_GAP = [(LO - 0.007, 12.0), (12.5, HI + 0.012)]


@pytest.mark.parametrize("ops, lead, busy", [
    (NEVER_IDLE, 0.0, 6.0),
    (ONE_GAP, 0.0, 5.5),
    (NEVER_IDLE, 1.5e-3, 6.0),
    (ONE_GAP, 1.5e-3, 5.5),
    ([(LO + 1.0, LO + 2.0)], 0.0, 1.0),
    # an operation that the device's clock puts 1.5 ms before the marker
    # and the host's inside it: all of it is the window's
    ([(LO + 0.0005, LO + 1.0)], 1.5e-3, 0.9995),
], ids=["never_idle", "one_gap", "never_idle_clock_leads",
        "one_gap_clock_leads", "inside", "starts_at_the_edge_clock_leads"])
def test_busy_time_is_what_the_device_did_inside_the_marker(ops, lead, busy):
    trace = a_trace(ops, lead)
    assert xplane.clock_shift_s(trace) == pytest.approx(lead, abs=1e-12)
    w = xplane.traced_window(trace)
    assert (w.lo, w.hi) == (LO, HI) and w.window_s == 6.0
    assert w.busy_s == pytest.approx(busy, abs=1e-9)
    assert 0 < w.busy_s <= w.window_s


def test_a_whole_trace_reading_passes_the_window_and_the_marker_cannot():
    trace = a_trace(NEVER_IDLE)
    # what run.py took before: every operation of the trace against 6 s
    assert xplane.busy_seconds(trace) == pytest.approx(6.019)
    assert xplane.busy_seconds(trace) > xplane.traced_window(trace).window_s
    assert xplane.traced_window(trace).busy_s == 6.0


@pytest.mark.parametrize("marks, found", [((), 0),
                                          (((LO, HI), (HI + 1, HI + 2)), 2)],
                         ids=["no_marker", "two_markers"])
def test_a_trace_without_exactly_one_marker_is_an_error(marks, found):
    with pytest.raises(ValueError, match=f"holds {found} spans called "
                                         f"'{WINDOW}'"):
        xplane.traced_window(a_trace(NEVER_IDLE, marks=marks))


@pytest.mark.parametrize("lead", [0.0, 1.5e-3], ids=["", "clock_leads"])
def test_the_breakdown_is_of_the_same_window(lead):
    # busy LO-7ms..11, 11.2..12, 12.5..15.9 and again after the window
    ops = [(LO - 0.007, 11.0), (11.2, 12.0), (12.5, 15.9),
           (HI + 0.001, HI + 0.012)]
    host = [E("engine.step", 10.9, 0.35), E("engine.fetch_tokens", 11.0, 0.1),
            E("engine.yield", 12.0, 4.5)]
    trace = a_trace(ops, lead, host=host)
    w = xplane.traced_window(trace)
    gaps = xplane.idle_gaps(trace, lo=w.lo, hi=w.hi)
    assert gaps == [pytest.approx(g) for g in
                    [(11.0, 11.2), (12.0, 12.5), (15.9, 16.0)]]
    assert w.busy_s + sum(b - a for a, b in gaps) == pytest.approx(w.window_s)
    by = xplane.attribute_gaps(trace, threads=["llm-engine-step"],
                               lo=w.lo, hi=w.hi)
    # the marker itself is no span: the gap at the end is the yield's
    assert by == pytest.approx({"engine.fetch_tokens": 0.1,
                                "engine.step": 0.1, "engine.yield": 0.6})
    assert sum(xplane.op_times(trace, w.lo, w.hi).values()) == \
        pytest.approx(w.busy_s)
    # whole, as the per-event readers take it: the gap past the window too
    assert sum(b - a for a, b in xplane.idle_gaps(trace)) == \
        pytest.approx(0.2 + 0.5 + 0.101)
    assert sum(xplane.op_times(trace).values()) == pytest.approx(
        w.busy_s + 0.007 + 0.011)


def test_nested_operations_keep_their_self_time_when_cut():
    # a `while` of 2 s holding two bodies; the window opens inside the
    # first body and closes inside the second
    ops = [E("%while.1 = (s32[]) while()", 9.0, 2.0),
           E("%fusion.1 = bf16[8]{0} fusion()", 9.2, 0.5),
           E("%fusion.2 = bf16[8]{0} fusion()", 10.2, 0.6)]
    trace = Trace({}, {0: ops}, {"python3": [E(WINDOW, 9.5, 1.0)]}, {})
    got = xplane.op_times(trace, 9.5, 10.5)
    assert got == pytest.approx({"fusion:fusion": 0.2 + 0.3,
                                 "while:while": 0.5})
    assert xplane.traced_window(trace).busy_s == pytest.approx(1.0)


def test_two_host_threads_of_one_name_are_both_kept(monkeypatch):
    """Were the second to replace the first, the marker could go with it:
    in the recorded trace the marker's thread and the feeder's are both
    called `python3`."""
    assert {"python3", "python3#3"} <= set(xplane.load(BUSY).host)
    import types
    import jax.profiler
    ns = types.SimpleNamespace

    def line(name, *events):
        return ns(name=name, events=[
            ns(name=n, start_ns=a * 1e9, duration_ns=(b - a) * 1e9, stats=[])
            for n, a, b in events])
    space = ns(planes=[ns(name="/host:CPU", lines=[
        line("python3", (WINDOW, LO, HI)),
        line("python3", ("engine.step", 11.0, 12.0))])])
    monkeypatch.setattr(jax.profiler, "ProfileData",
                        ns(from_file=lambda path: space))
    trace = xplane.load("any.xplane.pb")
    assert sorted(trace.host) == ["python3", "python3#1"]
    w = xplane.traced_window(trace)
    assert (w.window_s, w.busy_s) == (pytest.approx(6.0), 0.0)


def test_no_work_share_counts_a_wait_as_far_as_it_lies_inside():
    import importlib.util
    from benchmarks.harness import spans
    spec = importlib.util.spec_from_file_location(
        "m_no_work", os.path.join(ROOT, "benchmarks", "metrics",
                                  "engine.no_work_share.chat.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    waits = [E(spans.WAIT, LO - 1.0, 1.6), E(spans.WAIT, 12.0, 0.3),
             E(spans.WAIT, HI - 0.3, 2.0), E(spans.STEP, 11.0, 1.0)]
    run = {"_spans": spans.Reading(waits, {}, 0.0),
           "result": {"traced": {"lo": LO, "hi": HI, "window_s": HI - LO}}}
    assert mod.read(run) == pytest.approx(100.0 * (0.6 + 0.3 + 0.3) / 6.0)


def test_the_recorded_busy_device_overhangs_the_marker_and_reads_inside_it():
    trace = xplane.load(BUSY)
    w = xplane.traced_window(trace)
    assert w.window_s == pytest.approx(0.251006, rel=1e-5)
    assert xplane.clock_shift_s(trace) == pytest.approx(1.572e-3, rel=1e-3)
    # 71 executions of 4.2 ms, 12 of them before the marker opened
    first, _ = xplane.device_span(trace)
    assert w.lo - first == pytest.approx(0.05105, rel=1e-3)
    # so the old reading (all of the trace against the sleep) passes 1 ...
    whole = xplane.busy_seconds(trace)
    assert whole == pytest.approx(0.299837, rel=1e-5) and whole > w.window_s
    # ... and the window's own cannot, on a device that never paused
    assert w.busy_s == pytest.approx(0.250454, rel=1e-5)
    assert 0 < w.busy_s <= w.window_s and w.busy_s / w.window_s > 0.997
    gaps = xplane.idle_gaps(trace, lo=w.lo, hi=w.hi)
    assert gaps == []                       # none of 50 us: 9 us a program
    assert sum(xplane.op_times(trace, w.lo, w.hi).values()) == \
        pytest.approx(w.busy_s, rel=1e-9)
    assert xplane.top(xplane.op_times(trace, w.lo, w.hi))[0] == [
        "fusion:convolution_multiply_fusion",
        pytest.approx(0.247731, rel=1e-5)]


def test_a_traced_rehearsal_ends_in_a_line_that_parses():
    """No device plane on the CPU: nothing was busy, which a rehearsal's
    line already says is no measurement; the marker is found all the same."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "internlm2-1.8b.serve.batch", "--seed", "1",
         "--seconds", "1.2", "--trace", "1", "--rehearse", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    dev = line["device"]
    assert dev["busy_s"] == 0.0 and 0.59 < dev["window_s"] < 0.8
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
