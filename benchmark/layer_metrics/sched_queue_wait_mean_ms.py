"""Scheduler: mean milliseconds a request waited from ``submit`` to the
launch of the first dispatch that carried one of its prefill segments
(pending, waiting for pages, for budget, for the running execution to
end), over the requests whose wait ended inside the window.  The program
counts both where the dispatch is built (``/stats.dispatch_trace``)."""
from layer_metrics import delta


def read(ctx):
    total = delta(ctx, "dispatch_trace", "queue_wait_ms_sum")
    count = delta(ctx, "dispatch_trace", "queue_wait_count")
    return total / count if total is not None and count else None
