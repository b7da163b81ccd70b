"""Percentile and rate arithmetic of the end-to-end metrics, including
requests still waiting when the window closes."""

import numpy as np
import pytest

from bench import stats
from bench.stats import Record


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 100])
def test_percentile_matches_numpy_linear(q, n):
    xs = list(np.random.default_rng(n).exponential(size=n))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def _records():
    # window [10, 20)
    return [
        Record(0, 8, due=5.0, times=[11.0, 12.0]),        # due before
        Record(1, 8, due=10.0, times=[10.5, 11.0, 11.5]),  # 0.5 s
        Record(2, 8, due=12.0, times=[14.0, 19.0, 21.0]),  # 2 s
        Record(3, 8, due=15.0, times=[]),                  # still waiting
        Record(4, 8, due=19.0, times=[25.0]),              # first after close
        Record(5, 8, due=20.0, times=[20.5]),              # due after
    ]


def test_ttft_counts_waiting_requests_with_their_wait_so_far():
    got = sorted(stats.ttft_samples(_records(), 10.0, 20.0))
    # 1: 0.5; 2: 2.0; 3: waits 20 - 15 = 5; 4: waits 20 - 19 = 1
    assert got == pytest.approx([0.5, 1.0, 2.0, 5.0])
    p95 = stats.percentile(got, 95)
    assert p95 == pytest.approx(2.0 + 0.85 * 3.0)


def test_itl_takes_gaps_with_both_tokens_inside_the_window():
    got = sorted(stats.itl_samples(_records(), 10.0, 20.0))
    # 0: 11->12; 1: 10.5->11, 11->11.5; 2: 14->19 (19->21 crosses the close)
    assert got == pytest.approx([0.5, 0.5, 1.0, 5.0])


def test_output_rate_counts_tokens_that_arrived_in_the_window():
    recs = _records()
    assert stats.tokens_in(recs, 10.0, 20.0) == 2 + 3 + 2
    assert stats.output_rate(recs, 10.0, 20.0) == pytest.approx(0.7)


def test_due_in_is_half_open():
    assert [r.rid for r in stats.due_in(_records(), 10.0, 20.0)] == [1, 2, 3, 4]


@pytest.mark.parametrize("q,want", [(50, 1.5e3),
                                    (95, (2.0 + 0.85 * 3.0) * 1e3)])
def test_ttft_ms_is_the_percentile_of_the_samples(q, want):
    assert stats.ttft_ms(_records(), 10.0, 20.0, q) == pytest.approx(want)


def test_ttft_ms_of_a_window_with_nothing_due_is_none():
    assert stats.ttft_ms(_records()[:1], 10.0, 20.0, 95) is None


@pytest.mark.parametrize("t,want", [(9.0, 1), (11.5, 0), (13.0, 1),
                                    (16.0, 1), (19.5, 2), (30.0, 0)])
def test_waiting_at_counts_due_requests_without_a_first_token(t, want):
    # 0 waits 5-11, 1 waits 10-10.5, 2 waits 12-14, 3 waits 15-29,
    # 4 waits 19-25, 5 waits 20-20.5
    recs = _records()
    recs[3].times = [29.0]
    assert stats.waiting_at(recs, t) == want
