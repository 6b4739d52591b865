"""The balance of the routing over the decode steps: an expert layer's
busiest expert's (row, expert) pairs over the layer's mean, the mean of that
over the layers (/debug/state's ``moe.decode.pairs`` at the run's end: since
the runner started, the warm-up's steps among them - the harness keeps no
sample of it from the window's start, and the balance is the seed's router
and bias, not the traffic). 1 is even. A program with no such counter gives
None."""


def read(ctx):
    pairs = (((ctx.state_end or {}).get("moe") or {}).get("decode")
             or {}).get("pairs") or ()
    ratios = [max(p) * len(p) / sum(p) for p in pairs if sum(p)]
    return sum(ratios) / len(ratios) if ratios else None
