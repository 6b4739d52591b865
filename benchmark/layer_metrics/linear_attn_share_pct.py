"""Device time of the operations under the scope ``layer/linear_attn`` (the
linear layers' convolution, delta-rule update and state write) over the
device time of the decode programs (benchmark/layer_metrics/_linear_attn.py).
benchmark/reduce_named.py counts the same operations as ``unscoped``."""

from benchmark.layer_metrics._linear_attn import summary


def read(ctx):
    t = summary(ctx)
    if not t or not t["decode_module_s"] or not t["decode_linear_attn_s"]:
        return None
    return 100.0 * t["decode_linear_attn_s"] / t["decode_module_s"]
