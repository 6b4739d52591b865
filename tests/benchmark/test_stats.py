"""Percentile arithmetic, and a failed request ranked as +inf."""

import math
import types

import pytest

from benchmark import e2e_metrics, stats
from benchmark.loadgen import Record, Run
from benchmark.traffic_lib import Request


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (100, 5.0),
                                    (25, 2.0), (85, 4.4), (95, 4.8)])
def test_percentile_interpolates_like_numpy(q, want):
    assert stats.percentile([5, 1, 4, 2, 3], q) == pytest.approx(want)


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 50) is None
    assert stats.finite(None) is None and stats.finite(math.inf) is None
    assert stats.finite(2.5) == 2.5


def test_failed_requests_rank_as_inf_not_dropped():
    sample = stats.with_failures([10.0] * 8, 2)      # 10 requests, 2 failed
    assert len(sample) == 10
    assert stats.percentile(sample, 50) == 10.0
    assert stats.percentile(sample, 75) == 10.0      # rank 6.75 of 0..9
    assert stats.percentile(sample, 85) == math.inf  # rank 7.65 touches a failure
    assert stats.percentile(sample, 100) == math.inf


def _rec(due, first, last, done, n, ok=True, in_window=True):
    r = Record(Request([5] * 10, n, due), in_window, due=due, sent=due)
    r.first, r.last, r.done = first, last, done
    r.prompt_tokens, r.completion_tokens = 10, n if ok else n - 1
    return r


def test_end_to_end_metrics_from_records():
    recs = [_rec(0.0, 0.1, 1.1, 1.2, 11),                 # ttft 100 ms, tpot 100 ms
            _rec(1.0, 1.3, 2.3, 2.4, 21),                 # ttft 300, tpot 50
            _rec(9.0, 9.2, 11.0, 11.1, 10),               # finishes after the window
            _rec(-1.0, -0.9, 0.5, 0.6, 5, in_window=False)]  # warm-up
    events = [(0.5, 4), (9.99, 6), (10.0, 100), (-0.5, 50)]
    run = Run(0.0, 10.0, 1000.0, recs, events)
    assert e2e_metrics.compute("ttft_p50_ms", run) == pytest.approx(200.0)
    assert e2e_metrics.compute("ttft_p100_ms", run) == pytest.approx(300.0)
    assert e2e_metrics.compute("tpot_p50_ms", run) == pytest.approx(75.0)
    assert e2e_metrics.out_tok_s(run) == pytest.approx(1.0)   # 10 tokens in 10 s
    assert e2e_metrics.attempted_failed(run) == (3, 0)
    with pytest.raises(KeyError):
        e2e_metrics.compute("no_such_metric", run)


def test_truncated_request_fails_and_misses_every_percentile():
    recs = [_rec(0.0, 0.1, 1.1, 1.2, 11), _rec(1.0, 1.3, 2.3, 2.4, 21, ok=False)]
    run = Run(0.0, 10.0, 1000.0, recs, [])
    assert e2e_metrics.attempted_failed(run) == (2, 1)
    assert e2e_metrics.compute("ttft_p85_ms", run) == math.inf
    assert e2e_metrics.compute("tpot_p85_ms", run) == math.inf
    assert stats.finite(e2e_metrics.compute("ttft_p85_ms", run)) is None
    # a prompt the server counted differently is a failure too
    bad = _rec(2.0, 2.1, 2.5, 2.6, 5)
    bad.prompt_tokens = 11
    assert not bad.ok
