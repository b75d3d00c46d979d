"""Percentile and rate arithmetic on hand-made samples."""
import pytest

from benchmarks.harness import stats


def test_nearest_rank_percentile():
    xs = [10, 20, 30, 40, 50, 60, 70, 80, 90, 100]
    assert stats.percentile(xs, 90) == 90      # 9 of 10 at or below
    assert stats.percentile(xs, 91) == 100
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([5], 98) == 5
    assert stats.percentile([], 90) is None
    with pytest.raises(ValueError):
        stats.percentile(xs, 0)


def test_percentile_ignores_order():
    assert stats.percentile([3, 1, 2], 67) == 3
    assert stats.percentile([3, 1, 2], 66) == 2


def test_rate_is_work_over_the_whole_window():
    assert stats.rate_per_s(10920, 51.0) == pytest.approx(214.1176470588)
    with pytest.raises(ValueError):
        stats.rate_per_s(1, 0)


def test_token_gaps():
    assert stats.token_gaps([1.0, 1.5, 1.75]) == [0.5, 0.25]
    assert stats.token_gaps([1.0]) == []


def test_spread_as_the_bound_rule_reads_it():
    # statistics.quantiles(n=4) of 1..6: q1 = 1.75, q3 = 5.25, median 3.5
    assert stats.iqr_share([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)


def test_window_metrics_on_hand_made_arrivals():
    from benchmarks.harness.serve_cell import Collector, Sent, window_metrics
    c = Collector()
    c.arrivals = {"a": [10.2, 10.3, 10.5], "b": [11.0, 12.5], "c": []}
    sent = {"a": Sent(None, 10.0, 10.01), "b": Sent(None, 10.5, 10.5),
            "c": Sent(None, 11.9, 11.95)}
    w = window_metrics(c, sent, 10.0, 2.0)      # window [10, 12]
    assert w["ttft_s"] == pytest.approx([0.2, 0.5])
    assert w["no_first_token"] == 1
    assert w["tokens_in_window"] == 4           # b's second token is late
    assert w["gap_s"] == pytest.approx([0.1, 0.2])
    assert w["lag_s"] == pytest.approx([0.01, 0.0, 0.05])
