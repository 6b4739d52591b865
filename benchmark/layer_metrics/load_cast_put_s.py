"""Seconds of LoadModel spent handing weights to the device: the
``load_cast`` spans (``jnp.asarray(arr, dtype)``: the cast and, off a mesh,
the copy in one host call), ``load_put`` (mesh placement) and the one
``load_device_wait`` at the end (see _load_spans.py)."""

from benchmark.layer_metrics._load_spans import seconds


def read(ctx):
    return seconds(ctx, ("load_cast", "load_put", "load_device_wait"))
