"""Tails timed from when each request was due."""
import math

import pytest

from bench import stats


def test_nearest_rank_by_hand():
    values = [5.0, 1.0, 4.0, 2.0, 3.0, 10.0, 6.0, 8.0, 7.0, 9.0]
    assert stats.nearest_rank(values, 90) == 9.0
    assert stats.nearest_rank(values, 50) == 5.0
    assert stats.nearest_rank(values, 100) == 10.0
    assert stats.nearest_rank([3.0], 90) == 3.0
    with pytest.raises(ValueError):
        stats.nearest_rank([], 90)


def test_waits_count_from_due_not_from_submission():
    # due at 0, 1, 2, ...; the service took each in 5 s late and answered
    # 0.1 s after taking it in: every wait is 5.1 s, not 0.1 s
    due = [float(i) for i in range(10)]
    first = [d + 5.1 for d in due]
    assert stats.due_percentile(due, first, 90, gave_up=100.0) == \
        pytest.approx(5.1)


def test_a_stalled_request_never_improves_the_tail():
    due = [float(i) for i in range(10)]
    first = [d + 0.5 for d in due]
    answered = stats.due_percentile(due, first, 90, gave_up=70.0)
    first[3] = None                      # never answered
    stalled = stats.due_percentile(due, first, 90, gave_up=70.0)
    assert stalled >= answered
    # two of ten stalled: the 90th percentile falls on one of them, and
    # reads at least the longest wait seen, from its due time to giving up
    first[7] = None
    assert stats.due_percentile(due, first, 90, gave_up=70.0) == \
        pytest.approx(70.0 - 3.0)
    assert not math.isinf(stats.due_percentile(due, first, 100,
                                               gave_up=70.0))
