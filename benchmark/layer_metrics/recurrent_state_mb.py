"""Device bytes the engine holds as per-slot recurrent state beside the
K/V pages (``recurrent_state_bytes`` of the runner's /debug/state), in MB.
A program that reports no such counter gives None."""


def read(ctx):
    b = (ctx.state_end or {}).get("recurrent_state_bytes")
    return None if b is None else b / 1e6
