"""The ``mla_paged_decode`` kernel's share of its roofline: the larger of the
least HBM bytes of its calls in the capture over the HBM peak - every latent
row the live slots held read once, as published (the family's
``mla_decode_least``) - and the absorbed products' operations over the
bfloat16 peak, against the kernel's own device time in those calls. The rows
of a call are its burst's (benchmark/layer_metrics/_ling.py: the rows the
live slots held before the burst's first step, so at most what the device
read), and it cannot pass 100%. Where the jax.numpy form runs there is no
such call and the metric is not reported."""

from benchmark import roofline, spec
from benchmark.layer_metrics._ling import summary


def read(ctx):
    t = summary(ctx)
    if not t or not t["mla_matched_s"] or not t["mla_live_slot_calls"]:
        return None
    hf = ctx.cell.config
    fam = spec.family_of(hf)
    if not hasattr(fam, "mla_decode_least"):
        return None
    peaks = roofline.peaks(ctx.device["kind"])
    nbytes, flops = fam.mla_decode_least(hf, t["mla_ctx_rows"],
                                         t["mla_live_slot_calls"])
    least = max(nbytes / peaks["hbm_bytes_per_s"],
                flops / peaks["bf16_flops_per_s"])
    return 100.0 * least / t["mla_matched_s"]
