"""Interval arithmetic the trace reduction rests on."""

import pytest

from benchlib import intervals


def test_union_merges_overlaps_and_drops_empty():
    assert intervals.union([(3, 4), (0, 2), (1, 2.5), (5, 5)]) == [
        (0, 2.5), (3, 4)]
    assert intervals.length([(0, 2), (1, 3), (10, 11)]) == pytest.approx(4)


def test_intersection_len():
    a, b = [(0, 4), (6, 8)], [(3, 7)]
    assert intervals.intersection_len(a, b) == pytest.approx(2)


def test_gaps_are_what_nothing_covers():
    assert intervals.gaps([(1, 2), (1.5, 3), (5, 6)], 0, 7) == [
        (0, 1), (3, 5), (6, 7)]
    assert intervals.gaps([], 0, 1) == [(0, 1)]
