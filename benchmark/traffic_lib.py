"""What every traffic generator shares: mid-quantiles of the stated
distributions, the stratified deal, token contents, and the schedule's
shape.

A generator module under ``benchmark/generators/`` exposes

    generate(params, seconds, seed, vocab_size, context_size) -> Schedule

``params`` is the traffic file. For a fixed (params, seconds) every seed
yields the same multiset of work — the same request count, the same
(prompt, output) length pairs, the same gaps — and the seed only orders
them and fills the token contents.
"""

from __future__ import annotations

import dataclasses
import math
import random
from statistics import NormalDist
from typing import List, Optional

FIRST_TOKEN_ID = 3          # 0..2 are <unk>, <s>, </s> in the benchmark's tokenizer
CONTEXT_MARGIN = 8          # rows kept free so that no request context-shifts


@dataclasses.dataclass
class Request:
    prompt_ids: List[int]
    max_tokens: int
    due: Optional[float]     # seconds from the window's start; None in a closed loop
    tag: str = ""            # what the generator knows about it (doc, ask)

    @property
    def prompt_tokens(self) -> int:
        return len(self.prompt_ids)


@dataclasses.dataclass
class Schedule:
    mode: str                # "open" | "closed"
    warmup: List[Request]    # before the window; never measured
    window: List[Request]    # open: all due inside [0, seconds); closed: the replay list
    clients: int = 0         # closed loop only
    warmup_s: float = 0.0


def mid_quantiles(dist: dict, n: int) -> List[int]:
    """The n mid-quantiles (i + 0.5)/n of ``dist``, as whole token counts,
    ascending. dist: {"dist": "lognormal", median, sigma, min, max} or
    {"dist": "uniform", min, max}."""
    lo, hi = int(dist["min"]), int(dist["max"])
    kind = dist["dist"]
    out = []
    for i in range(n):
        p = (i + 0.5) / n
        if kind == "lognormal":
            x = float(dist["median"]) * math.exp(
                float(dist["sigma"]) * NormalDist().inv_cdf(p))
        elif kind == "uniform":
            x = lo + (hi - lo) * p
        else:
            raise ValueError(f"unknown distribution {kind!r}")
        out.append(min(hi, max(lo, int(round(x)))))
    return out


def exponential_gaps(n: int, total: float) -> List[float]:
    """The n mid-quantiles of an exponential, rescaled to sum to
    ``total`` (so the rate is n / total exactly), ascending."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    k = total / sum(raw)
    return [g * k for g in raw]


def stratified_order(n: int, block: int, rng: random.Random) -> List[int]:
    """A permutation of range(n) for items given in ascending order: the
    ranks are dealt round-robin into blocks of about ``block``, so every
    block spans the whole range; the seed shuffles inside a block and the
    order of the blocks, nothing else."""
    nb = max(1, round(n / max(1, block)))
    blocks = [list(range(b, n, nb)) for b in range(nb)]
    for b in blocks:
        rng.shuffle(b)
    rng.shuffle(blocks)
    return [r for b in blocks for r in b]


def coprime_stride(n: int) -> int:
    """A fixed stride near n/phi that is coprime with n: pairs quantile
    i of one distribution with quantile (i * stride) % n of another, the
    same pairing for every seed."""
    if n <= 2:
        return 1
    s = max(1, round(n * 0.6180339887))
    while math.gcd(s, n) != 1:
        s += 1
    return s % n or 1


def length_pairs(first: dict, second: dict, n: int, context_size: int):
    """n (first, second) length pairs, ascending in ``first``; the pairing
    does not depend on the seed. The second length is cut where the pair
    would not fit the slot's context."""
    a = mid_quantiles(first, n)
    b = mid_quantiles(second, n)
    s = coprime_stride(n)
    room = context_size - CONTEXT_MARGIN
    pairs = []
    for i in range(n):
        out = min(b[(i * s) % n], room - a[i])
        if out < 1:
            raise ValueError(f"a prompt of {a[i]} tokens leaves no room in a "
                             f"context of {context_size}")
        pairs.append((a[i], out))
    return pairs


def arrivals(n: int, seconds: float, block: int, rng: random.Random):
    """n due times inside (0, seconds): stratified exponential gaps, each
    request due half its own gap after the previous gap's end, so the
    gaps sum to the window and nothing is due on its edge."""
    gaps = exponential_gaps(n, seconds)
    order = stratified_order(n, block, rng)
    t, due = 0.0, []
    for r in order:
        due.append(t + gaps[r] / 2.0)
        t += gaps[r]
    return due


def tokens(rng: random.Random, n: int, vocab_size: int) -> List[int]:
    return [rng.randrange(FIRST_TOKEN_ID, vocab_size) for _ in range(n)]


def text_of(ids: List[int]) -> str:
    """The prompt text whose tokenization, by the benchmark's word-level
    tokenizer, is exactly ``ids``."""
    return " ".join(f"t{i}" for i in ids)
