"""Seconds of stall the streams saw: the sum of ``overdue_ms`` of the
``late_dispatch`` spans that began inside the window (a dispatch that waited
more than three times its program kind's own pace, and a quarter second),
in seconds. 0 in a healthy run. A program that records no such span is one
whose ``sync_wait`` spans carry no ``kind``: it gives None."""


def read(ctx):
    if not any(s["name"] == "sync_wait" and "kind" in (s.get("args") or {})
               for s in ctx.spans):
        return None
    return sum(s["args"]["overdue_ms"] for s in ctx.spans
               if s["name"] == "late_dispatch") / 1e3
