"""Device time of the operations under the scopes ``layer/mlp/router`` and
``layer/mlp/experts`` (the routed expert feed-forward: scores and choice, the
grouped products and the weighted sum back) over the device time of the
decode programs (benchmark/layer_metrics/_moe.py). benchmark/reduce_named.py
counts the same operations under ``layer/mlp``, beside the dense layers'."""

from benchmark.layer_metrics._moe import summary


def read(ctx):
    t = summary(ctx)
    if not t or not t["decode_module_s"] or not t["decode_experts_s"]:
        return None
    return 100.0 * (t["decode_router_s"] + t["decode_experts_s"]) \
        / t["decode_module_s"]
