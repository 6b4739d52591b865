"""Open loop at a fixed rate: unshared prompts, stratified lengths and gaps.

Traffic file keys: rate_per_s, warmup_s, block, prompt_tokens, output_tokens.
"""

import random

from benchmark import traffic_lib as tl


def _phase(p, n, seconds, offset, rng, vocab_size, context_size):
    pairs = tl.length_pairs(p["prompt_tokens"], p["output_tokens"], n,
                            context_size)
    order = tl.stratified_order(n, int(p["block"]), rng)
    due = tl.arrivals(n, seconds, int(p["block"]), rng)
    return [tl.Request(tl.tokens(rng, pairs[r][0], vocab_size), pairs[r][1],
                       offset + t) for r, t in zip(order, due)]


def generate(params, seconds, seed, vocab_size, context_size):
    rate = float(params["rate_per_s"])
    warm_s = float(params["warmup_s"])
    n = max(1, round(rate * seconds))
    n_warm = max(1, round(rate * warm_s))
    rng = random.Random(seed)
    window = _phase(params, n, seconds, 0.0, rng, vocab_size, context_size)
    warm = _phase(params, n_warm, warm_s, -warm_s, rng, vocab_size,
                  context_size)
    return tl.Schedule("open", warm, window, warmup_s=warm_s)
