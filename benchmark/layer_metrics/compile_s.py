"""Seconds the runner's CompileTracker counted before the warm mark (from
the persistent cache after a checkout's first run)."""


def read(ctx):
    c = (ctx.state_end or {}).get("compiles") or {}
    return c.get("compile_seconds_total")
