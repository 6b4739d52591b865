"""Device time of the operations under ``lm_head``, ``sample``,
``spec_draft`` and ``spec_verify`` - the decode program around the layers -
over the device time of the decode programs (benchmark/reduce_named.py)."""

from benchmark.reduce_named import named, scope_share_pct


def read(ctx):
    return scope_share_pct(named(ctx), ("lm_head", "sample", "spec_draft",
                                        "spec_verify"))
