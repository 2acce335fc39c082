"""Window accounting on a synthetic event log."""
import math

import pytest

from benchmarks.harness import window
from benchmarks.harness.window import Stream


def stream(idx, due, times, max_tokens=None, **kw):
    return Stream(idx=idx, due=due, sent=due + 0.001, token_times=times,
                  max_tokens=len(times) if max_tokens is None
                  else max_tokens, **kw)


def test_tokens_count_by_arrival_not_by_request():
    t0, t1 = 10.0, 20.0
    streams = [
        stream(0, 5.0, [9.0, 9.5, 10.0, 10.5, 11.0], done=True),  # from before
        stream(1, 12.0, [12.5, 13.0, 13.5], done=True),           # inside
        stream(2, 19.0, [19.5, 20.0, 20.5, 21.0]),                # past the end
        stream(3, 25.0, [25.5]),                                  # after
    ]
    # 3 of the first, 3 of the second, 2 of the third (edges included)
    assert window.tokens_in_window(streams, t0, t1) == 8
    assert window.attempted_failed(streams, t0, t1) == (2, 0)


def test_ttft_is_from_due_time_and_failures_are_worst():
    t0, t1 = 0.0, 10.0
    streams = [stream(0, 1.0, [1.2, 1.3]),
               stream(1, 2.0, [2.5]),
               stream(2, 3.0, [], max_tokens=4, error="HTTP 503"),
               stream(3, 4.0, [], max_tokens=4)]          # nothing yet
    ttft = window.ttft_ms(streams, t0, t1)
    assert ttft[0] == pytest.approx(200.0)
    assert ttft[1] == pytest.approx(500.0)
    assert math.isinf(ttft[2]) and math.isinf(ttft[3])
    assert math.isinf(window.percentile(ttft, 95))
    assert window.attempted_failed(streams, t0, t1) == (4, 1)


def test_gaps_belong_to_the_window_of_their_later_event():
    s = [stream(0, 0.0, [0.9, 1.1, 1.2, 2.1])]
    assert window.gaps_ms(s, 1.0, 2.0) == pytest.approx([200.0, 100.0])


def test_short_answer_is_a_failure_only_when_the_server_ended_it():
    cut = stream(0, 1.0, [1.1, 1.2], max_tokens=5, done=True)
    open_ = stream(1, 1.0, [1.1, 1.2], max_tokens=5)
    assert window.attempted_failed([cut, open_], 0.0, 2.0) == (2, 1)


def test_percentile_and_spread():
    assert window.percentile([1, 2, 3, 4, 5], 50) == 3
    assert window.percentile(list(range(101)), 95) == pytest.approx(95)
    vals = [100, 101, 99, 100.5, 99.5, 100]
    assert 0 < window.iqr_spread(vals) < 0.02


def test_mean_gap_reader_weighs_every_gap_alike():
    import importlib
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "readers"))
    reader = importlib.import_module("client_mean")
    run = {"kind": "serve", "t0": 1.0, "t1": 2.0,
           "streams": [stream(0, 0.0, [0.9, 1.1, 1.2, 2.1]),
                       stream(1, 0.0, [1.0, 1.6])]}
    # gaps ending in the window: 200, 100 and 600 ms
    assert reader.read(run, what="gap") == pytest.approx(300.0)
    assert reader.read(dict(run, streams=[]), what="gap") is None
    assert reader.read({"kind": "train"}, what="gap") is None
