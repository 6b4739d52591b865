"""Seconds of the window's start that the engine's span ring no longer held
when ``/debug/trace`` was read: ``trace.oldest_retained_epoch`` of
``/debug/state`` (the wall time from which the ring is complete) less the
window's start, floored at 0. A guard: above 0, the span metrics
(``queue_wait_mean_ms``, ``prefill_step_ms_p50``, ...) cover only part of
the window."""


def read(ctx):
    t = ((ctx.state_end or {}).get("trace") or {}).get(
        "oldest_retained_epoch")
    if t is None:
        return None
    return max(0.0, float(t) - ctx.run.w0_wall)
