"""Device time of the decode programs (``jit_decode_burst``, and
``jit_spec_tick`` where speculation runs) over the steps they ran: each
execution in the capture is joined, through the clock anchor, to the
``decode_burst_device`` ring span of its dispatch, which says how many
steps it ran (benchmark/reduce_named.py)."""

from benchmark.reduce_named import named


def read(ctx):
    m = (named(ctx) or {}).get("decode_matched")
    if not m or not m.get("steps"):
        return None
    return 1e3 * m["module_s"] / m["steps"]
