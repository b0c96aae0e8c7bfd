"""Token stamps to end-to-end metrics."""

import math
import types

import pytest

from benchmark import reduce


def rec(stamps, *, due=0.0, midlife=False, output=None, outcome="budget",
        finished=None):
    return types.SimpleNamespace(
        stamps=list(stamps), tokens=[1] * len(stamps), due=due,
        midlife=midlife, output=len(stamps) if output is None else output,
        outcome=outcome, finished=finished, prompt=[0] * 4)


def test_percentile_interpolates_and_sorts_infinity_last():
    assert reduce.percentile([1, 2, 3, 4], 0.5) == 2.5
    assert reduce.percentile([5], 0.99) == 5
    assert reduce.percentile([1, 2, math.inf], 0.5) == 2
    assert reduce.percentile([1, math.inf, math.inf], 0.5) == math.inf
    with pytest.raises(ValueError):
        reduce.percentile([], 0.5)


def test_tokens_are_counted_by_their_stamps_inside_the_window():
    recs = [rec([9.9, 10.0, 10.5, 19.99, 20.0]), rec([12.0])]
    assert reduce.tokens_in(recs, 10.0, 20.0) == 4
    e2e = reduce.end_to_end(recs, 10.0, 20.0, setup_s=3.0)
    assert e2e["out_tok_s"]["value"] == pytest.approx(0.4)
    assert e2e["setup_s"] == {"value": 3.0, "unit": "s"}


def test_gaps_need_both_stamps_inside_and_stay_within_a_request():
    recs = [rec([9.0, 10.0, 10.25, 10.75, 20.5]), rec([10.1, 10.2])]
    assert sorted(reduce.token_gaps(recs, 10.0, 20.0)) == pytest.approx(
        [0.1, 0.25, 0.5])


def test_ttft_runs_from_the_due_time_and_unserved_requests_count():
    recs = [rec([11.0], due=10.5), rec([15.0], due=12.0),
            rec([], due=19.0),                    # unserved at the close
            rec([10.2], due=9.0),                 # due before the window
            rec([10.3], due=10.1, midlife=True)]  # pre-filled, not timed
    got = reduce.ttfts(recs, 10.0, 20.0)
    assert sorted(got) == [0.5, 3.0, math.inf]
    e2e = reduce.end_to_end(recs, 10.0, 20.0, 1.0)
    assert e2e["ttft_p50_ms"]["value"] == pytest.approx(3000.0)
    # Per started block of 256 prompt tokens: 600 tokens are 3 blocks.
    long = rec([13.0], due=10.0)
    long.prompt = [0] * 600
    assert reduce.ttfts_per_unit([long], 10.0, 20.0) == pytest.approx([1.0])
    assert e2e["ttft_per_256tok_p50_ms"]["value"] == pytest.approx(3000.0)


def test_failures_count_finished_requests_only_and_catch_short_ones():
    recs = [rec([1.0, 2.0], finished=2.0),
            rec([1.0], output=2, finished=3.0),             # truncated
            rec([1.0, 2.0], outcome="deadline", finished=3.0),
            rec([1.0], output=5, finished=None),            # cut by the close
            rec([1.0], output=5, outcome="cancelled", finished=11.0)]
    assert reduce.failures(recs, 10.0) == {"attempted": 3, "failed": 2}


def test_halves_split_the_window_in_two():
    recs = [rec([0.5, 1.5, 2.5, 3.5, 3.6, 3.7])]
    assert reduce.halves(recs, 0.0, 4.0) == {
        "first_half_tok_s": 1.0, "second_half_tok_s": 2.0}
