"""Per-layer metric readers, one module per metric, found by its name.

Each exposes ``read(obs) -> float | None``. ``obs`` holds ``trace`` (a
``bench.tracing.TraceSummary`` of the traced part of the window, or None)
and ``counters`` (the driver's counts). A reader that finds nothing to
read returns None, and the metric is left out of the result line.
"""
