"""How late the load generator ran: send time minus due time, 95th
percentile over the window's requests. A starved generator reads as a fast
server, so this stands beside every open-loop latency."""

from benchmark import stats


def read(ctx):
    lag = [(r.sent - r.due) * 1e3 for r in ctx.run.records if r.in_window]
    return stats.percentile(lag, 95) if ctx.schedule.mode == "open" else None
