"""Totals of the runner's ``load_*`` spans (backend/runner.py::_load,
engine/weights.py), from ``/debug/state``'s ``trace.by_span_ms``: totals
per span name that survive the ring's wrap."""


def seconds(ctx, names):
    by = ((ctx.state_end or {}).get("trace") or {}).get("by_span_ms")
    if not by or "load_model" not in by:
        return None          # the program records no load spans
    return sum(by[n]["total_ms"] for n in names if n in by) / 1e3
