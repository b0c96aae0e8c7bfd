"""The two generator kinds, driven without an engine."""

import itertools
import os

import pytest

from benchmark.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = Spec(os.path.join(ROOT, "BENCHMARK.json"))


def generator(kind, params, slots):
    return SPEC.load_module("generators", kind + ".py").Generator(
        params, slots)


def test_backlog_tops_up_to_slots_plus_backlog_and_never_below():
    g = generator("backlog", {"backlog_x_slots": 2}, 8)
    assert len(g.due(0.0, 8, None)) == 16       # 8 in flight -> 16 waiting
    assert g.due(1.0, 24, None) == []
    assert g.due(2.5, 21, None) == [2.5, 2.5, 2.5]
    assert g.next_due() is None


def test_paced_due_times_are_the_running_sum_of_gaps_over_the_rate():
    gaps = itertools.cycle([0.5, 1.5, 1.0])
    g = generator("paced", {"rate_per_s": 2.0}, 8)
    assert g.due(0.2, 0, lambda: next(gaps)) == []
    assert g.next_due() == pytest.approx(0.25)
    assert g.due(1.6, 0, lambda: next(gaps)) == pytest.approx(
        [0.25, 1.0, 1.5])
    assert g.next_due() == pytest.approx(1.75)
    # Load does not slow an open loop.
    assert g.due(1.8, 10 ** 6, lambda: next(gaps)) == pytest.approx([1.75])


def test_paced_rate_must_be_positive():
    with pytest.raises(ValueError):
        generator("paced", {"rate_per_s": 0}, 8)
