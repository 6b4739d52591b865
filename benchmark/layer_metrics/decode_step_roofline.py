"""The decode step's share of its roofline: the least time the chip could
take for one step (benchmark/roofline.py: every weight once at its stored
width plus every live KV row, over the HBM peak; or the operations over
the bf16 peak, whichever is larger) over the device time of a step in the
profiler capture (device time of the programs in which paged-decode
attention ran, over the steps they ran)."""

from benchmark import roofline, stats


def read(ctx):
    t = ctx.trace
    if not t or not t.get("decode_steps"):
        return None
    live = [s for s in ctx.state_samples if s["slots_active"]]
    if not live:
        return None
    batch = stats.mean(s["slots_active"] for s in live)
    kv_tokens = stats.mean(s["kv_rows"] for s in live)
    hf = ctx.cell.config
    wbytes = 1 if hf["precision"]["weights"] == "int8" else 2
    peak = roofline.peaks(ctx.device["kind"])
    least = roofline.least_seconds(
        roofline.decode_step_least_bytes(hf, wbytes, kv_tokens, batch),
        roofline.decode_step_least_flops(hf, kv_tokens, batch), peak)
    return 100.0 * least / (t["decode_module_s"] / t["decode_steps"])
