"""Seconds inside the ``load_source`` spans of LoadModel (the runner's own
clock; see _load_spans.py and PERF.md section 3)."""

from benchmark.layer_metrics._load_spans import seconds


def read(ctx):
    return seconds(ctx, ("load_source",))
