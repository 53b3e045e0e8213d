"""Sample arithmetic of the benchmark: percentiles, sample counts, and
the serving metrics taken from the load generator's records."""

import pytest

import _paths  # noqa: F401
from harness import stats


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4, 5], 0, 1.0),
    ([1, 2, 3, 4, 5], 100, 5.0),
    ([10, 20], 90, 19.0),
    ([7], 95, 7.0),
    (list(range(1, 101)), 90, 90.1),
])
def test_percentile_interpolates_between_order_statistics(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 90) is None


@pytest.mark.parametrize("n,want", [(128, 92), (100, 90), (200, 95),
                                    (1000, 99), (50, 80), (19, None)])
def test_highest_percentile_with_ten_samples_beyond(n, want):
    assert stats.highest_supported_percentile(n) == want


def test_iqr_spread_uses_statistics_quantiles():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    # statistics.quantiles(n=4) of these: q1 100.75, q3 104.25
    assert stats.iqr_spread(vals) == pytest.approx(3.5 / 102.5)


def _rec(due, sent, first, chunks, expected, ok=True):
    return {"due": due, "sent": sent, "first": first, "chunks": chunks,
            "expected": expected, "ok": ok, "error": None if ok else "x"}


def test_ttft_counts_from_the_due_time_not_the_send_time():
    # due at 10.0, sent 0.3 s late, first token at 10.5
    m = stats.serving_metrics(
        [_rec(10.0, 10.3, 10.5, [[10.5, 1]], 1)], t0=10.0, seconds=5.0)
    assert m["ttft_p90_ms"] == pytest.approx(500.0)
    assert m["late_p50_ms"] == pytest.approx(300.0)


def test_closed_loop_ttft_counts_from_the_send_time():
    m = stats.serving_metrics(
        [_rec(None, 10.3, 10.5, [[10.5, 1]], 1)], t0=10.0, seconds=5.0)
    assert m["ttft_p90_ms"] == pytest.approx(200.0)
    assert m["late_p50_ms"] is None


def test_failed_request_counts_as_the_windows_length():
    recs = [_rec(0.0, 0.0, 0.1, [[0.1, 1]], 1),
            _rec(1.0, 1.0, None, [], 4, ok=False)]
    m = stats.serving_metrics(recs, t0=0.0, seconds=8.0)
    assert m["attempted"] == 2 and m["failed"] == 1
    assert m["ttft_p90_ms"] == pytest.approx(100.0 + 0.9 * 7900.0)
    assert m["ttft_mean_ms"] == pytest.approx((100.0 + 8000.0) / 2)
    assert m["ttft_ms"] == [100.0, 8000.0]


def test_ttft_mean_is_over_every_request_due_in_the_window():
    recs = [_rec(float(i), float(i), i + 0.1 * (i + 1), [[i + 1.0, 1]], 1)
            for i in range(4)]
    m = stats.serving_metrics(recs, t0=0.0, seconds=8.0)
    assert m["n_ttft"] == 4
    assert m["ttft_mean_ms"] == pytest.approx(250.0)
    assert stats.serving_metrics([], 0.0, 8.0)["ttft_mean_ms"] is None


def test_a_chunk_of_k_tokens_is_k_equal_gaps_and_window_bounds_tokens():
    recs = [_rec(0.0, 0.0, 1.0, [[1.0, 1], [1.3, 3], [9.0, 1]], 5)]
    m = stats.serving_metrics(recs, t0=0.0, seconds=5.0)
    # the chunk at 9.0 lies outside the window: neither tokens nor gaps
    assert m["tokens_in_window"] == 4
    assert m["n_gaps"] == 3
    assert m["itl_p50_ms"] == pytest.approx(100.0)
    assert m["output_tokens_per_s"] == pytest.approx(4 / 5.0)
