"""Median ``prefill_device`` span (dispatch to the sync worker's ready
mark) of the packed-prefill dispatches that began inside the window."""

from benchmark import stats


def read(ctx):
    return stats.percentile([s["dur_ms"] for s in ctx.spans
                             if s["name"] == "prefill_device"], 50)
