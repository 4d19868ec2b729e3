"""Percentile and window arithmetic on synthetic stamps."""

import pytest

from benchlib import window


def test_rate_is_all_steps_over_all_time():
    stamps = [1.0 + 0.1 * i for i in range(1, 101)]      # 100 steps, 10 s
    assert window.rate(1.0, stamps, 256) == pytest.approx(100 * 256 / 10.0)


def test_every_step_but_the_first_gives_one_reading():
    stamps = [0.1 * i for i in range(1, 32)]             # 0.1 s steps
    times = window.step_times(stamps)
    assert len(times) == 30
    assert all(t == pytest.approx(0.1) for t in times)
    assert window.summary(0.0, stamps) == (31, pytest.approx(3.1), 30)


def test_one_slow_step_shows_as_itself():
    stamps, t = [], 0.0
    for i in range(40):
        t += 0.3 if i in (10, 20, 30) else 0.1
        stamps.append(t)
    times = window.step_times(stamps)
    assert max(times) == pytest.approx(0.3)
    assert window.percentile(times, 50) == pytest.approx(0.1)
    # three slow steps of 39 are the 95th percentile's business
    assert window.percentile(times, 95) > 0.25


def test_no_step_no_reading():
    assert window.step_times([]) == [] and window.step_times([5.0]) == []


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (95, 4.8), (100, 5.0)])
def test_percentile_is_linear_between_order_statistics(q, want):
    assert window.percentile([5.0, 1.0, 3.0, 2.0, 4.0], q) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        window.percentile([], 50)
