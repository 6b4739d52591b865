"""One reader per per-layer metric: ``read(ctx) -> float | None``.

``ctx`` (benchmark/run.py::Context) carries what a traced run collected:
``run`` (the load generator's records), ``spans`` (the engine's RingTracer
spans that began inside the window, from /debug/trace), ``state_samples``
(/debug/state every half second of the window), ``counters0`` /
``counters1`` (/metrics at the window's start and end), ``state_end``,
``timings`` (the harness's own clocks), ``trace`` (reduce_trace's summary of
the profiler capture), ``cell`` and ``device``. A reader that finds nothing
to read returns None and the harness leaves the metric out of the line.
"""
