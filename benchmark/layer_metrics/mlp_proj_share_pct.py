"""Device time of the operations under the scopes ``layer/mlp`` and
``layer/attn_proj`` (the weight matmuls: where ``mat()`` shows) over the
device time of the decode programs (benchmark/reduce_named.py)."""

from benchmark.reduce_named import named, scope_share_pct


def read(ctx):
    return scope_share_pct(named(ctx), ("layer/mlp", "layer/attn_proj"))
