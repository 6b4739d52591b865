"""Device idle time in the profiler capture during which the host was at
work - inside any ``tick_*`` phase of the engine loop other than
``tick_idle_wait``, or the sync worker inside ``sync_wait`` - over the
capture's length (benchmark/reduce_named.py; gaps under 20 us left out)."""

from benchmark.reduce_named import named


def read(ctx):
    t = named(ctx)
    if not t or not t.get("window_s"):
        return None
    return 100.0 * t["idle_host_bound_s"] / t["window_s"]
