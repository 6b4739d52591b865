"""Device idle time in the profiler capture during which the engine loop
was parked in ``tick_idle_wait`` with nothing queued (and no host work
overlapped it), over the capture's length: with ``host_bound_idle_pct`` it
sums to the idle share, less gaps under 20 us (benchmark/reduce_named.py)."""

from benchmark.reduce_named import named


def read(ctx):
    t = named(ctx)
    if not t or not t.get("window_s"):
        return None
    return 100.0 * t["idle_no_work_s"] / t["window_s"]
