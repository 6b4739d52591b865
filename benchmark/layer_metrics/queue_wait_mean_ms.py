"""Mean of the scheduler's ``queue_wait`` spans (submit to slot admission)
that began inside the window."""

from benchmark import stats


def read(ctx):
    return stats.mean(s["dur_ms"] for s in ctx.spans
                      if s["name"] == "queue_wait")
