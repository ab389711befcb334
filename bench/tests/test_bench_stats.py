"""Percentile and window arithmetic of the client records."""

import math
import random

import numpy as np
import pytest

from lib import stats


def rec(sched, first=None, times=(), status="FINISHED", http=200):
    return {"sched": sched, "first": first, "times": list(times),
            "status": status, "http": http}


def test_percentile_matches_numpy():
    rng = random.Random(0)
    xs = [rng.random() for _ in range(37)]
    for q in (0, 10, 50, 90, 95, 99, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_misses_count_as_infinite_ttft():
    reqs = [rec(0.1 * i, first=0.1 * i + 0.05) for i in range(9)]
    reqs.append(rec(0.95, first=None, status=None, http=None))  # no answer
    v = stats.ttft_values(reqs, 0.0, 1.0)
    assert len(v) == 10 and v.count(math.inf) == 1
    assert math.isinf(stats.percentile(v, 95))
    assert stats.percentile(v, 50) == pytest.approx(0.05)


def test_refused_and_failed_requests_are_misses():
    reqs = [rec(0.1, first=0.2, status="REJECTED"),
            rec(0.2, first=0.3, http=500, status="HTTP_ERROR"),
            rec(0.3, first=0.35)]
    v = stats.ttft_values(reqs, 0.0, 1.0)
    assert sorted(v)[:1] == [pytest.approx(0.05)] and v.count(math.inf) == 2
    assert stats.attempted_failed(reqs, 0.0, 1.0) == (3, 2)


def test_requests_outside_the_window_are_left_out():
    reqs = [rec(-0.5, first=0.1), rec(0.5, first=0.6), rec(1.5, first=1.6)]
    assert len(stats.ttft_values(reqs, 0.0, 1.0)) == 1
    assert stats.attempted_failed(reqs, 0.0, 1.0) == (1, 0)


def test_cut_request_with_a_token_is_not_a_failure():
    reqs = [rec(0.2, first=0.3, times=[0.3, 0.4], status="CUT")]
    assert stats.attempted_failed(reqs, 0.0, 1.0) == (1, 0)


def test_request_cut_while_queued_is_not_a_failure():
    """A closed loop's request still waiting for a slot at the close."""
    reqs = [rec(0.8, status="CUT", http=None),
            rec(0.9, status="CUT", http=200)]
    assert stats.attempted_failed(reqs, 0.0, 1.0) == (2, 0)


def test_tokens_and_gaps_counted_inside_the_window_only():
    reqs = [rec(-1.0, first=-0.5, times=[-0.5, -0.1, 0.2, 0.7, 1.3]),
            rec(0.1, first=0.4, times=[0.4, 0.9])]
    assert stats.tokens_in_window(reqs, 0.0, 1.0) == 4
    gaps = stats.itl_values(reqs, 0.0, 1.0)
    assert sorted(gaps) == pytest.approx([0.3, 0.5, 0.5])
