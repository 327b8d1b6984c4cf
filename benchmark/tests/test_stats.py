"""Percentiles, whole-cycle accounting and scrape deltas."""

import pytest

from harness import stats


def test_percentile_interpolates_between_order_statistics():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0.5) == 30.0
    assert stats.percentile(xs, 0.0) == 10.0
    assert stats.percentile(xs, 1.0) == 50.0
    assert stats.percentile(xs, 0.95) == pytest.approx(48.0)
    assert stats.percentile(reversed(xs), 0.25) == 20.0
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_tail_keeps_ten_samples_beyond_it():
    q, v = stats.tail(list(range(1000)))
    assert v == 989 and q == pytest.approx(989 / 999)
    assert stats.tail([3.0, 1.0, 2.0]) == (0.0, 1.0)
    assert stats.tail([7.0]) == (1.0, 7.0)


def test_whole_cycles_leaves_out_the_partial_ones():
    # (time, cumulative records, checkpoints seen): cycles complete
    # between t=2..3, t=6..7 and t=10..11
    events = [(1, 10, 0), (2, 20, 0), (3, 30, 1), (5, 40, 1), (7, 50, 2),
              (9, 60, 2), (11, 70, 3), (12, 80, 3)]
    assert stats.whole_cycles(events) == (40, 8, 2)
    assert stats.whole_cycles(events[:4]) is None
    assert stats.whole_cycles([]) is None


TEXT = """# HELP pilosa_x x
pilosa_http_request_duration_seconds_sum{method="POST",route="post_query"} 2.5
pilosa_http_request_duration_seconds_count{method="POST",route="post_query"} 10
pilosa_http_request_duration_seconds_sum{method="POST",route="post_sql"} 0.5
pilosa_http_request_duration_seconds_sum{method="POST",route="post_import"} 9
pilosa_ops_pallas_fallback_total{kernel="topn",why="mesh"} 3
pilosa_recovery_checkpoint_seconds_count 4
pilosa_quoted{v="a\\"b"} 1
"""


def test_parse_and_sum_series():
    s = stats.parse_metrics(TEXT)
    name = "http_request_duration_seconds_sum"
    assert stats.series_sum(s, name) == 12.0
    assert stats.series_sum(s, name, {"route": "post_sql"}) == 0.5
    assert stats.series_sum(
        s, name, label_in={"route": ["post_query", "post_sql"]}) == 3.0
    assert stats.series_sum(s, "recovery_checkpoint_seconds_count") == 4.0
    assert stats.series_sum(s, "no_such_series") == 0.0
    assert stats.series_sum(s, "quoted") == 1.0
    # a name matches whole or after the server's prefix, never inside
    assert stats.series_sum(s, "seconds_sum") == 0.0


def test_delta_is_after_minus_before_and_new_series_count_from_zero():
    before = stats.parse_metrics(TEXT)
    after = stats.parse_metrics(TEXT.replace(" 2.5", " 4.0") + (
        'pilosa_ops_pallas_fallback_total{kernel="bsi_sum",why="mesh"} 2\n'))
    assert stats.delta(before, after,
                       "http_request_duration_seconds_sum") == 1.5
    assert stats.delta(before, after, "ops_pallas_fallback_total") == 2.0
