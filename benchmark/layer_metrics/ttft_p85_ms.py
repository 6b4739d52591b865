"""The 85th percentile of time to first token over the window's requests
(a failed one is +inf), as a per-layer metric of the service where the cell
cannot carry it as an end-to-end metric: with the requests one window holds
its run-to-run spread is wider than half of any bound the contract allows
(PERF.md section 2)."""

from benchmark import e2e_metrics


def read(ctx):
    return e2e_metrics.compute("ttft_p85_ms", ctx.run)
