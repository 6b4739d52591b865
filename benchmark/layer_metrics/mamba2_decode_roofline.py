"""The ``mamba2_decode`` kernel's share of its roofline: the least HBM bytes
its calls in the capture must move - the state of the slots LIVE in each call
read once and written once (the family's ``mamba2_decode_least_bytes``; a
call is one mamba layer of one step, so the calls' live slots over the mamba
layers are live slots x steps) - over the HBM peak, against the kernel's own
device time in those calls. The live slots of a call are its burst's
(benchmark/layer_metrics/_ssm.py): a kernel that moved the slots that are not
live would read low, and it cannot pass 100%. Where the jax.numpy form runs
there is no such call and the metric is not reported."""

from benchmark import roofline, spec
from benchmark.layer_metrics._ssm import summary


def read(ctx):
    t = summary(ctx)
    if not t or not t["matched_kernel_s"] or not t["live_slot_calls"]:
        return None
    hf = ctx.cell.config
    fam = spec.family_of(hf)
    n_ssm = list(hf["layer_types"])[:hf["num_hidden_layers"]].count("mamba")
    least = fam.mamba2_decode_least_bytes(hf, t["live_slot_calls"] / n_ssm) \
        / roofline.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / t["matched_kernel_s"]
