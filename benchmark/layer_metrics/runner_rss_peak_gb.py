"""The most the runner process ever held resident, in GB: ``VmHWM`` of its
``/proc/self/status`` as /debug/state gives it at the run's end
(``host_memory.rss_peak_bytes``). The peak stands in the load, so it moves
``setup_s`` (and decides whether the machine's memory holds the run at all).
A program with no such record gives None."""


def read(ctx):
    b = ((ctx.state_end or {}).get("host_memory") or {}).get("rss_peak_bytes")
    return None if b is None else b / 1e9
