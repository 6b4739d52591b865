"""Closed loop: ``clients`` callers, each sending its next request when the
last completes, replaying one stratified list of lengths in a seeded order.

Traffic file keys: clients, warmup_s, block, list_per_s, prompt_tokens,
output_tokens. The list holds round(list_per_s * seconds) requests — more
than the system completes in the window — and is replayed from its start if
it runs out, so every seed offers the same work in another order.
"""

import random

from benchmark import traffic_lib as tl


def generate(params, seconds, seed, vocab_size, context_size):
    warm_s = float(params["warmup_s"])
    n = max(int(params["clients"]),
            round(float(params["list_per_s"]) * (seconds + warm_s)))
    rng = random.Random(seed)
    pairs = tl.length_pairs(params["prompt_tokens"], params["output_tokens"],
                            n, context_size)
    order = tl.stratified_order(n, int(params["block"]), rng)
    reqs = [tl.Request(tl.tokens(rng, pairs[r][0], vocab_size), pairs[r][1],
                       None) for r in order]
    return tl.Schedule("closed", [], reqs, clients=int(params["clients"]),
                       warmup_s=warm_s)
