"""The trace reduction on the recorded trace: three executions of a tiny
program on a v5e, 20 ms of host sleep between them
(benchmarks/tests/data/record_trace.py printed the numbers used here)."""
import os

import pytest

from benchmarks.harness import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny_v5e.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xplane.load(DATA)


def test_per_program_time(trace):
    times = xplane.program_times(trace)
    assert list(times) == ["jit_tiny_step"]
    assert times["jit_tiny_step"] == pytest.approx(
        [15.843e-6, 15.886e-6, 16.185e-6], rel=1e-3)


def test_busy_is_the_union_of_operation_intervals(trace):
    busy = xplane.busy_seconds(trace)
    # inside the three programs (47.9 us) less the gaps between their ops
    assert busy == pytest.approx(45.05e-6, rel=2e-3)
    lo, hi = xplane.device_span(trace)
    assert hi - lo == pytest.approx(43.39e-3, rel=1e-3)
    assert busy / (hi - lo) < 0.002            # idle nearly all the time


def test_union_merges_overlaps():
    assert xplane.union_intervals([(0, 2), (1, 3), (5, 6), (6, 7)]) == [
        (0, 3), (5, 7)]


def test_operations_are_labelled_by_kind(trace):
    ops = xplane.op_times(trace)
    assert ops["kernel:tiny_step"] == pytest.approx(30.59e-6, rel=1e-3)
    assert "fusion:convolution_reduce_fusion" in ops
    assert len(xplane.kernel_events(trace, "tpu_custom_call")) == 3


def test_gaps_go_to_what_the_host_was_doing(trace):
    assert xplane.clock_shift_s(trace) == pytest.approx(1.507e-3, rel=1e-2)
    gaps = xplane.idle_gaps(trace)
    assert len(gaps) == 2
    by = xplane.attribute_gaps(trace, threads=["python3"])
    assert max(by, key=by.get) == "$time sleep"
    assert by["$time sleep"] == pytest.approx(0.0412, rel=2e-2)
    assert sum(by.values()) == pytest.approx(
        sum(b - a for a, b in gaps), rel=1e-6)
