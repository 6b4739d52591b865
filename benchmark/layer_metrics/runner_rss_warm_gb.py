"""What the runner process held resident when ``LoadModel`` returned, in GB
(``host_memory.at_warm.rss_bytes`` of /debug/state): what a load leaves
behind on the host for as long as the model is served. A program with no
such record gives None."""


def read(ctx):
    warm = ((ctx.state_end or {}).get("host_memory") or {}).get("at_warm")
    b = (warm or {}).get("rss_bytes")
    return None if b is None else b / 1e9
