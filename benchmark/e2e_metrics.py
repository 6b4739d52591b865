"""The end-to-end metrics, from the load generator's records.

One function per metric name in BENCHMARK.json's ``end_to_end`` (``setup_s``
is the harness's own clock). A later PR that adds an end-to-end metric adds
a function here only if the records cannot already express it; the four
below cover latency percentiles of any rank through their names:
``ttft_p<q>_ms``, ``tpot_p<q>_ms``.
"""

from __future__ import annotations

import re

from benchmark import stats
from benchmark.loadgen import Run


def ttft_sample(run: Run):
    """ms from due to first token, over the requests due inside the
    window; a failed one is +inf."""
    win = [r for r in run.records if r.in_window]
    good = [(r.first - r.due) * 1e3 for r in win if r.ok]
    return stats.with_failures(good, sum(not r.ok for r in win))


def tpot_sample(run: Run):
    """ms per output token after the first, over the window's requests
    that also finish inside it; a failed window request is +inf."""
    win = [r for r in run.records if r.in_window]
    good = [(r.last - r.first) * 1e3 / (r.completion_tokens - 1)
            for r in win
            if r.ok and r.done <= run.w1 and r.completion_tokens > 1]
    failed = sum(1 for r in win if not r.ok and r.error != "cancelled")
    return stats.with_failures(good, failed)


def out_tok_s(run: Run) -> float:
    n = sum(k for t, k in run.token_events if run.w0 <= t < run.w1)
    return n / (run.w1 - run.w0)


def compute(name: str, run: Run):
    m = re.fullmatch(r"(ttft|tpot)_p(\d+)_ms", name)
    if m:
        sample = ttft_sample(run) if m.group(1) == "ttft" else tpot_sample(run)
        return stats.percentile(sample, float(m.group(2)))
    if name == "out_tok_s":
        return out_tok_s(run)
    raise KeyError(f"no end-to-end metric {name!r}")


def attempted_failed(run: Run):
    win = [r for r in run.records if r.in_window]
    failed = [r for r in win if not r.ok and r.error != "cancelled"]
    return len(win), len(failed)
