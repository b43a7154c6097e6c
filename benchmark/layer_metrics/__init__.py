"""Per-layer metric readers, found by the metric's name in
``BENCHMARK.json``.  ``read(ctx)`` takes the run's context (``/stats``
at the window's edges and at the trace's, client records, the reduced
trace; see the README) and returns the value, or ``None`` where there is
nothing to read: the harness then leaves the metric out of the line."""


def delta(ctx, section: str, key: str, a: str = "stats_open",
          b: str = "stats_close"):
    """Growth of one ``/stats`` counter between two snapshots (the
    window's edges, or ``stats_trace_start`` / ``stats_trace_stop``)."""
    def snap(name):
        return ctx[name] if name in ctx else ctx["marks"][name]
    try:
        return snap(b)[section][key] - snap(a)[section][key]
    except (KeyError, TypeError):
        return None
