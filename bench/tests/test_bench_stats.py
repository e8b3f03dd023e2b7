"""Nearest-rank percentiles and the window arithmetic of the series."""
import pytest

import stats


@pytest.mark.parametrize("q,want", [(50, 5), (90, 9), (95, 10), (100, 10),
                                    (1, 1), (10, 1), (11, 2)])
def test_nearest_rank_on_one_to_ten(q, want):
    assert stats.nearest_rank(range(10, 0, -1), q) == want


def test_nearest_rank_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.nearest_rank([], 50)


def test_window_keeps_stamps_in_half_open_interval():
    stamps = {1: [0.9, 1.0, 1.5, 2.0], 2: [1.99, 2.5]}
    assert stats.tokens_in_window(stamps, 1.0, 2.0) == 3
    # a gap counts only where both of its tokens lie in the window
    assert stats.gaps_in_window(stamps, 1.0, 2.0) == [0.5]


def test_ttft_series_from_due_time_and_missing():
    due = {1: 0.5, 2: 1.2, 3: 1.8, 4: 2.5}
    first = {1: 0.7, 2: 1.5, 4: 2.6}
    served, missing = stats.ttft_series(due, first, 1.0, 2.0)
    assert served == [pytest.approx(0.3)]
    assert missing == [3]


def test_quartile_spread_uses_python_quartiles():
    assert stats.quartile_spread([1, 2, 3, 4, 5]) == pytest.approx(
        (4.5 - 1.5) / 3)
