import math
from types import SimpleNamespace as NS

from bench import stats


def test_nearest_rank_percentile():
    v = list(range(1, 101))
    assert stats.percentile(v, 0.90) == 90
    assert stats.percentile(v, 0.95) == 95
    assert stats.percentile([5.0], 0.9) == 5.0
    assert stats.percentile([3, 1, 2], 0.5) == 2


def test_infinite_samples_sort_last():
    v = [1.0] * 89 + [math.inf] * 11
    assert stats.percentile(v, 0.90) == math.inf
    v = [1.0] * 91 + [math.inf] * 9
    assert stats.percentile(v, 0.90) == 1.0


def _req(due, times, failed=False):
    return NS(due_at=due, token_times=times, failed=failed)


def test_ttft_counts_due_in_window_and_failures_as_infinite():
    reqs = [_req(0.5, [1.0, 1.2]), _req(2.0, [2.25]), _req(1.0, []),
            _req(1.5, [3.0], failed=True), _req(9.0, [9.5])]
    t = stats.ttfts(reqs, (0.0, 5.0))
    assert t == [0.5, 0.25, math.inf, math.inf]


def test_itl_gaps_inside_the_window_only():
    reqs = [_req(0, [0.5, 1.0, 1.5, 2.5]), _req(0, [3.0, 3.1])]
    assert stats.itl_gaps(reqs, (0.9, 3.05)) == [0.5, 1.0]

