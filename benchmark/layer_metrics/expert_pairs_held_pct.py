"""Of the (row, expert) pairs the decode steps routed, the share that went to
an expert THIS CHIP holds, from the runner's /debug/state (``moe.decode``:
``pairs`` over ``pairs_routed``, summed over the expert layers, since start).
With a strided quarter of the experts held it reads 25 if the seed's router
is even: the number that says whether two seeds did equal work. A program
that counts no routed pairs (every expert held, or no experts) gives
nothing to read."""


def read(ctx):
    d = ((ctx.state_end or {}).get("moe") or {}).get("decode") or {}
    routed = sum(d.get("pairs_routed") or ())
    if not routed:
        return None
    return 100.0 * sum(map(sum, d["pairs"])) / routed
