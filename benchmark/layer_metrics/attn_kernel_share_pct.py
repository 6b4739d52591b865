"""Device time of the paged-decode attention custom calls over the
device's busy time, in the profiler capture."""


def read(ctx):
    t = ctx.trace
    if not t or not t.get("busy_s"):
        return None
    return 100.0 * t["decode_kernel_s"] / t["busy_s"]
