"""The ``kda_decode`` kernel's share of its roofline: the least HBM bytes its
calls in the capture must move - the float32 state of the slots LIVE in each
call read once and written once (the family's ``kda_decode_least_bytes``; a
call is one KDA layer of one step) - over the HBM peak, against the kernel's
own device time in those calls. The live slots of a call are its burst's
(benchmark/layer_metrics/_ling.py): a kernel that moved the slots that are
not live would read low, and it cannot pass 100%. Where the jax.numpy form
runs there is no such call and the metric is not reported."""

from benchmark import roofline, spec
from benchmark.layer_metrics._ling import summary


def read(ctx):
    t = summary(ctx)
    if not t or not t["kda_matched_s"] or not t["kda_live_slot_calls"]:
        return None
    hf = ctx.cell.config
    fam = spec.family_of(hf)
    if not hasattr(fam, "kda_decode_least_bytes"):
        return None
    least = fam.kda_decode_least_bytes(hf, t["kda_live_slot_calls"]) \
        / roofline.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / t["kda_matched_s"]
