"""Window arithmetic: censored TTFT, open end gaps, multi-token deltas."""
import pytest

from bench.harness import window as w
from bench.harness.window import Stream


def s(rid, due, emits, done=None, sent=None):
    return Stream(rid, due, due if sent is None else sent, 10, list(emits), done)


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert w.percentile(v, 90) == 90
    assert w.percentile(v, 95) == 95
    assert w.percentile([3.0], 99) == 3.0
    with pytest.raises(ValueError):
        w.percentile([], 50)


def test_ttft_counts_requests_due_in_window_and_censors_the_waiting():
    streams = [
        s("before", 0.5, [(1.5, 1)]),  # due before the window: not counted
        s("served", 2.0, [(2.25, 1)]),
        s("late", 3.0, [(9.0, 1)]),  # first token after t1: enters at t1
        s("never", 4.0, []),  # no token: enters with its wait so far
    ]
    assert w.ttft(streams, 1.0, 5.0) == [0.25, 2.0, 1.0]


def test_itl_gaps_zero_for_extra_tokens_and_open_end():
    streams = [
        s("a", 0.0, [(0.5, 1), (1.5, 1), (2.0, 3), (2.5, 1)], done=2.5),
        s("b", 0.0, [(1.2, 1), (1.4, 1)]),  # still decoding at t1 = 3.0
    ]
    gaps = w.itl(streams, 1.0, 3.0)
    # a: 1.0 (gap ending inside), 0.5 + two zeros, 0.5; b: 0.2, open end 1.6
    assert sorted(gaps) == pytest.approx(sorted([1.0, 0.5, 0.0, 0.0, 0.5, 0.2, 1.6]))


def test_itl_counts_a_stall_spanning_the_whole_window():
    gaps = w.itl([s("stuck", 0.0, [(0.5, 1)])], 1.0, 4.0)
    assert gaps == [3.5]


def test_tokens_and_lateness_and_queue_waits():
    streams = [
        s("a", 1.0, [(1.5, 1), (2.0, 4), (5.5, 1)], sent=1.25),
        s("b", 4.5, [], sent=4.5),
    ]
    assert w.tokens(streams, 1.0, 5.0) == 5
    assert w.lateness(streams, 1.0, 5.0) == [0.25, 0.0]
