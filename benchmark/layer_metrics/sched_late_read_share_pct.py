"""Scheduler: the share of the window's dispatches whose output was there
before the host came to read it (``/stats.dispatch_trace``:
``late_reads`` over ``seq``).  The scheduler prepares the next dispatch
and drains the last one while the device executes; where that work
outlasts the execution the device waits for the host, inside the span
the record calls ``wait``, and the program says so itself: just before
the blocking read it asks the output whether it is ready.  0 where the
host is always done first; ``idle_late_read_attributed_pct`` says what
the late reads cost in idle seconds."""
from layer_metrics import delta


def read(ctx):
    late = delta(ctx, "dispatch_trace", "late_reads")
    total = delta(ctx, "dispatch_trace", "seq")
    return 100.0 * late / total if late is not None and total else None
