"""Median over the window's decode bursts of the ``decode_burst_device``
span divided by the steps in the burst. On the host clock, and a burst
waits behind the one in flight (pipeline depth 2), so this is the time a
token waits for, not the device time of a step; the roofline metric takes
that from the profiler."""

from benchmark import stats


def read(ctx):
    per = [s["dur_ms"] / s["args"]["steps"] for s in ctx.spans
           if s["name"] == "decode_burst_device"
           and (s.get("args") or {}).get("steps")]
    return stats.percentile(per, 50)
