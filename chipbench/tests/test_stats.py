"""Metric arithmetic: percentiles with their counts, goodput with misses,
TTFT, token gaps and tokens in the window."""
import numpy as np
import pytest

from chipbench import stats
from chipbench.workload import Step


def _step(due, times, finish, deadline, counted=True):
    s = Step(0, 0, (), 8, len(times), np.zeros(4, np.int32),
             np.zeros(4, np.int32))
    s.due, s.token_times, s.finish = due, list(times), finish
    s.deadline_s, s.counted = deadline, counted
    return s


def test_percentile_is_nearest_rank_and_counts_the_tail():
    v = list(range(1, 101))
    assert stats.percentile(v, 90) == 90
    assert stats.percentile(v, 95) == 95
    assert stats.beyond(v, 90) == 10
    assert stats.percentile([3.0], 99) == 3.0
    assert "over 100 samples, 10 beyond it" in stats.tail_line("x", v, 90)
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_goodput_counts_late_and_unfinished_as_misses():
    steps = [_step(0.0, [0.5, 1.0], 1.0, 2.0),          # met
             _step(1.0, [1.5, 4.0], 4.0, 2.0),          # late
             _step(2.0, [2.5], None, 5.0),              # never finished
             _step(3.0, [3.2, 3.4], 3.4, 1.0),          # met
             _step(0.0, [0.1], 0.1, 1.0, counted=False)]
    assert stats.goodput(steps, 10.0) == pytest.approx(0.2)
    assert [stats.met_deadline(s) for s in steps[:4]] == [True, False,
                                                          False, True]


def test_ttft_gaps_and_tokens_in_window():
    steps = [_step(0.0, [0.5, 0.5, 0.7], 0.7, 9.0),
             _step(1.0, [], None, 9.0),
             _step(0.0, [2.0, 3.0], 3.0, 9.0, counted=False)]
    # a step with no token yet counts the time up to the stop
    assert stats.ttfts(steps, end=4.0) == [0.5, 3.0]
    assert stats.token_gaps(steps) == pytest.approx([0.0, 0.2])
    assert stats.tokens_in(steps, 0.5, 2.5) == 4
