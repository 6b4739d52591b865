"""The tail of the decode step that ``tpot_p85_ms`` feels: over the window's
``decode_burst_device`` spans with ``steps``, the largest milliseconds a step
over the median (the same division ``decode_step_ms_p50`` makes). A window in
which one burst stalls reads several; a window with no such span gives
None."""

from benchmark import stats


def read(ctx):
    per = [s["dur_ms"] / s["args"]["steps"] for s in ctx.spans
           if s["name"] == "decode_burst_device"
           and (s.get("args") or {}).get("steps")]
    p50 = stats.percentile(per, 50)
    return max(per) / p50 if p50 else None
