"""Seconds of compiling that ``compile_s`` (the engine tracker's total)
leaves out of a load: ``compiles_process.unowned_seconds`` of /debug/state
at the run's end, the compile events of threads that bound no tracker. A
program with no such record gives None."""


def read(ctx):
    return ((ctx.state_end or {}).get("compiles_process") or {}).get(
        "unowned_seconds")
