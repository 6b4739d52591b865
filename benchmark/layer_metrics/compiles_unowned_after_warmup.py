"""Compiles after the runner's warm mark on a thread that no engine's
CompileTracker hears (``compiles_process.unowned_after_warmup`` of
/debug/state at the run's end): what ``compiles_after_warmup``, a condition
of ``correct``, cannot count. A program with no such record gives None."""


def read(ctx):
    return ((ctx.state_end or {}).get("compiles_process") or {}).get(
        "unowned_after_warmup")
