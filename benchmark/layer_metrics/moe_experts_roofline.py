"""The expert products' share of their roofline in the decode step: the
least HBM bytes of the steps a capture holds - each expert a step TOUCHED in
a layer read once, three projections (the family's ``moe_experts_least_bytes``
of the matched bursts' ``experts_touched``) - over the HBM peak, against the
device time of ``layer/mlp/experts`` in those same bursts
(benchmark/layer_metrics/_moe.py). At most what the device read: a form that
reads experts no row chose reads low, and it cannot pass 100%. A program
whose bursts report no ``experts_touched`` gives nothing to read."""

from benchmark import roofline, spec
from benchmark.layer_metrics._moe import summary


def read(ctx):
    t = summary(ctx)
    if not t or not t["matched_experts_s"] \
            or not t["matched_experts_touched"]:
        return None
    hf = ctx.cell.config
    least = spec.family_of(hf).moe_experts_least_bytes(
        hf, t["matched_experts_touched"]) \
        / roofline.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / t["matched_experts_s"]
