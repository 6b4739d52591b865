"""The decode-time delta-rule update's share of its roofline: the least
HBM bytes it must move for the decode steps in the capture (each live slot's
state read once and written once in every linear layer: the family's
``gated_delta_decode_least_bytes``) over the HBM peak, against its device
time there - the ``gated_delta_decode`` kernel's calls, or, where the
jax.numpy form runs and there is no such call, the whole scope
``layer/linear_attn``. Steps are counted from the capture itself: the
kernel's calls over the linear layers a step makes, or, without the kernel,
reduce_trace's count from the family's decode kernels. (Not the span-to-
program join of ``decode_program_ms_per_step``: its half-millisecond
tolerance loses this cell's 150 ms bursts, PERF.md section 7.)"""

from benchmark import roofline, spec
from benchmark.layer_metrics._linear_attn import live_slots, summary


def read(ctx):
    t = summary(ctx)
    batch = live_slots(ctx)
    if not t or not batch:
        return None
    hf = ctx.cell.config
    fam = spec.family_of(hf)
    n_lin = list(hf["layer_types"])[:hf["num_hidden_layers"]].count(
        "linear_attention")
    if t["decode_kernel_s"]:
        busy, steps = t["decode_kernel_s"], t["decode_kernel_calls"] / n_lin
    else:
        busy = t["decode_linear_attn_s"]
        steps = (ctx.trace or {}).get("decode_steps")
    if not busy or not steps:
        return None
    least = fam.gated_delta_decode_least_bytes(hf, batch) * steps \
        / roofline.peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / busy
