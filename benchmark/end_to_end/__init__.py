"""End-to-end metric readers, found by the metric's name in
``BENCHMARK.json``.  ``read(ctx)`` returns the value or ``None``."""
