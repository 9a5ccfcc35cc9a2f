"""One reader per per-layer metric, named as in ``BENCHMARK.json``.

Each module has ``read(ctx) -> float | None``; ``None`` means there was
nothing to read, and the metric is left out of the run's line.  ``ctx``
carries the reduced trace (``ctx["trace"]``, a
``perfbench.tracing.TraceSummary``), the window's counts and the cell's
configuration.
"""
