"""Exact percentiles, due-time latency, spreads — on synthetic schedules."""

import numpy as np
import pytest

from chipbench import stats


@pytest.mark.parametrize("q,want", [(50, 50.0), (99, 99.0), (100, 100.0),
                                    (1, 1.0), (99.5, 100.0)])
def test_percentile_is_nearest_rank_over_all_samples(q, want):
    a = np.arange(1, 101, dtype=float)
    np.random.default_rng(0).shuffle(a)
    assert stats.percentile_exact(a, q) == want


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile_exact([], 50)


def _served(due, stall_at=None, stall_s=0.0, service_s=0.001):
    """A single server that answers in arrival order, ``service_s`` each,
    and stops for ``stall_s`` at ``stall_at``."""
    free, recv = 0.0, []
    for t in due:
        start = max(t, free)
        if stall_at is not None and start >= stall_at and free < stall_at + stall_s:
            start = max(start, stall_at + stall_s)
        free = start + service_s
        recv.append(free)
    return np.array(recv)


def test_a_planted_stall_moves_the_tail_and_the_rate():
    due = np.arange(0, 10, 0.002)              # 500/s, open loop
    calm = stats.due_latency_ms(due, _served(due), 2000)
    stalled_recv = _served(due, stall_at=5.0, stall_s=0.5)
    stalled = stats.due_latency_ms(due, stalled_recv, 2000)
    assert stats.percentile_exact(calm, 99) == pytest.approx(1.0)
    # the stall holds back every request due behind it: 250 sent into a
    # 0.5 s hole wait on average half of it, so p99 sits near the hole
    assert stats.percentile_exact(stalled, 99) > 400
    assert stats.percentile_exact(stalled, 50) == pytest.approx(1.0, abs=0.5)
    # the rate over the whole window counts what was answered inside it
    window = 5.25
    assert (stalled_recv <= window).sum() < (_served(due) <= window).sum()


def test_latency_runs_from_the_due_time_not_the_send_time():
    due = np.array([0.0, 0.1])
    recv = np.array([0.05, 0.4])               # the second was sent late
    assert stats.due_latency_ms(due, recv, 2000).tolist() == pytest.approx(
        [50.0, 300.0])


def test_unanswered_and_timed_out_requests_sit_in_every_tail():
    due = np.zeros(100)
    recv = np.full(100, 0.001)
    recv[0] = np.nan                           # never answered
    recv[1] = 2.5                              # answered after the timeout
    lat = stats.due_latency_ms(due, recv, 2000)
    assert stats.failed(lat, 2000) == 2 and (lat[:2] == 4000).all()
    assert stats.percentile_exact(lat, 99) == 4000
    assert stats.percentile_exact(lat, 50) == pytest.approx(1.0)


def test_spread_is_the_quartile_distance_over_the_median():
    vals = [10.0, 10.2, 9.9, 10.1, 10.0, 10.3]
    import statistics
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.iqr_spread(vals) == pytest.approx((q3 - q1) / 10.05)
