"""Mean ``slots_active`` over the window's ``tick`` spans of the engine
loop that dispatched decode steps (``decode_tokens`` > 0): the batch a
decode step ran with, from the scheduler's own count."""

from benchmark import stats


def read(ctx):
    return stats.mean(
        s["args"]["slots_active"] for s in ctx.spans
        if s["name"] == "tick" and s["args"].get("decode_tokens", 0) > 0
        and "slots_active" in s["args"])
