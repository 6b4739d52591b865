"""Open loop of document sessions: each document is asked ``asks_per_doc``
times with a fresh question appended, the asks of one document at least
``asks_per_doc`` requests apart, and no document of the warm-up appears in
the window — so a document's first ask is a true miss and the others can
hit the prefix cache.

Traffic file keys: rate_per_s, warmup_s, asks_per_doc, ask_stride,
doc_tokens, question_tokens, output_tokens. Ask ``a`` of document ``j``
sits at position (A*j + stride*a) mod N of the N = A*D requests; with
stride = 1 (mod A) that is a bijection, and the first asks fall evenly,
one in every A requests, instead of in runs.
"""

import random

from benchmark import traffic_lib as tl


def positions(n_docs: int, asks: int, stride: int):
    """-> doc index per position, for N = asks * n_docs positions."""
    n = asks * n_docs
    if stride % asks != 1 % asks:
        raise ValueError("ask_stride must be 1 modulo asks_per_doc")
    seq = [None] * n
    for j in range(n_docs):
        for a in range(asks):
            seq[(asks * j + stride * a) % n] = j
    if min_spacing(seq) < min(asks, n_docs):
        # too few documents for the stride to wrap cleanly: plain rounds
        seq = [j for _ in range(asks) for j in range(n_docs)]
    return seq


def min_spacing(seq) -> int:
    """Smallest distance between two asks of one document."""
    last, best = {}, len(seq)
    for k, j in enumerate(seq):
        if j in last:
            best = min(best, k - last[j])
        last[j] = k
    return best


def _phase(p, n_docs, seconds, offset, rng, vocab_size, context_size):
    asks = int(p["asks_per_doc"])
    n = asks * n_docs
    seq = positions(n_docs, asks, int(p["ask_stride"]))
    doc_len = tl.mid_quantiles(p["doc_tokens"], n_docs)
    pairs = tl.length_pairs(p["question_tokens"], p["output_tokens"], n,
                            context_size - max(doc_len))
    # the document of length rank d always owns the (question, output)
    # pairs d, d + D, d + 2D, ...: the multiset of requests is fixed, and
    # the seed only says where a document sits and which ask gets which
    rank = list(range(n_docs))
    rng.shuffle(rank)
    own = []
    for d in rank:
        mine = [pairs[d + n_docs * a] for a in range(asks)]
        rng.shuffle(mine)
        own.append(mine)
    docs = [tl.tokens(rng, doc_len[d], vocab_size) for d in rank]
    due = tl.arrivals(n, seconds, 16, rng)
    seen, reqs = {}, []
    for j, t in zip(seq, due):
        ask = seen.get(j, 0)
        seen[j] = ask + 1
        q, out = own[j][ask]
        reqs.append(tl.Request(docs[j] + tl.tokens(rng, q, vocab_size), out,
                               offset + t, tag=f"doc{j}.ask{ask}"))
    return reqs


def generate(params, seconds, seed, vocab_size, context_size):
    rate = float(params["rate_per_s"])
    asks = int(params["asks_per_doc"])
    warm_s = float(params["warmup_s"])
    n_docs = max(1, round(rate * seconds / asks))
    n_warm = max(1, round(rate * warm_s / asks))
    rng = random.Random(seed)
    # the window's true span keeps the rate exact for a whole number of documents
    window = _phase(params, n_docs, seconds, 0.0, rng, vocab_size,
                    context_size)
    warm = _phase(params, n_warm, warm_s, -warm_s, rng, vocab_size,
                  context_size)
    return tl.Schedule("open", warm, window, warmup_s=warm_s)
