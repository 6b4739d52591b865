"""Device time of the operations under the scope ``layer/ssm`` (the mamba
layers' convolution, state update and state write) over the device time of
the decode programs (benchmark/layer_metrics/_ssm.py).
benchmark/reduce_named.py counts the same operations as ``unscoped``."""

from benchmark.layer_metrics._ssm import summary


def read(ctx):
    t = summary(ctx)
    if not t or not t["decode_module_s"] or not t["decode_ssm_s"]:
        return None
    return 100.0 * t["decode_ssm_s"] / t["decode_module_s"]
