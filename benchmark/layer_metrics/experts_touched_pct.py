"""The window's mean share of an expert layer's experts that a decode step
touches: the ``experts_touched`` of the window's ``decode_burst_device`` spans
(distinct experts a layer a step, summed over a burst's layers and steps)
over their steps x expert layers x experts (the last two from /debug/state's
``moe``). Where on the curve of experts touched against live rows the cell
sits: a step's bytes follow it. A program whose bursts report no such
argument gives None."""

from benchmark.layer_metrics._moe import window_bursts


def read(ctx):
    w = window_bursts(ctx)
    moe = (ctx.state_end or {}).get("moe") or {}
    layers = len((moe.get("decode") or {}).get("pairs") or ())
    if not w or not w[0] or not layers:
        return None
    return 100.0 * w[1] / (w[0] * layers * moe["experts"])
