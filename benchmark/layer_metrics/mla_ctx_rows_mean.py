"""The mean latent rows a live slot held when a decode burst began: the
window's ``decode_burst_device`` spans' ``ctx_rows`` (the rows their live
slots held before the burst's first step, summed) over the count of their
``slot_ids``. What one call of ``mla_paged_decode`` walks a slot, so what
``mla_decode_roofline`` has to be read beside when two runs are compared. A
program whose bursts carry no ``ctx_rows`` (no latent pool, or the parent of
the PR that brought the argument) gives None."""

BURST = "decode_burst_device"


def read(ctx):
    rows = slots = 0
    for s in ctx.spans:
        a = s.get("args") or {}
        if s["name"] == BURST and a.get("ctx_rows") is not None:
            rows += a["ctx_rows"]
            slots += len(a.get("slot_ids") or ())
    return rows / slots if slots else None
