"""Device time of the ``jit_prefill_*`` programs over the device's busy
time, in the profiler capture (benchmark/reduce_named.py)."""

from benchmark.reduce_named import named


def read(ctx):
    t = named(ctx)
    if not t or not t.get("busy_s") or not any(
            k.startswith("jit_") and k != "jit__lambda_"
            for k in t["modules"]):
        return None          # the program names no module
    return 100.0 * t["prefill_module_s"] / t["busy_s"]
