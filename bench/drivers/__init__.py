"""Traffic drivers, one module per kind of mix, found by the ``driver`` key
of a traffic file. Each exposes ``run(ctx) -> Outcome``."""
