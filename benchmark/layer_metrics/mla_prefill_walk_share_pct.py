"""Device time of the operations under the scope ``mla_walk``
(``ops/mla.py::prefill_attention``'s walk over a slot's committed latent
rows in a continued pack: each block of 512 rows expanded through ``W_kvb``
into per-head keys and values, scored against the pack's queries and summed)
over the device time of the prefill programs, from the profiler capture, as
``layer_metrics/hc_share_pct.py`` reads its scope off the decode programs.

    python -m benchmark.layer_metrics.mla_prefill_walk_share_pct <capture dir>

prints ``{"prefill_module_s", "prefill_walk_s"}`` as one JSON line. A
program that names no such scope (every family without a latent pool, and
the parent of the PR that brought the name) gives nothing to read, and the
metric is left out.
"""

from __future__ import annotations

import sys

from benchmark.layer_metrics import hc_share_pct as _share

SCOPE = "mla_walk"


def reduce(cap: dict) -> dict:
    module, walk = _share.scope_time(cap, SCOPE, decode=False)
    return {"prefill_module_s": module, "prefill_walk_s": walk}


def read(ctx):
    return _share.read_share(ctx, "mla_prefill_walk_share_pct",
                             "prefill_module_s", "prefill_walk_s")


if __name__ == "__main__":
    _share.main(reduce, sys.argv)
