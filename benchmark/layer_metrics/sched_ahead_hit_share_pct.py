"""Scheduler: the share of the window's dispatches that were launched as
prepared: packed, and their arrays put on the device, while the previous
execution ran, so that the gap before them held the validation and the
call and not the drain, the intake and the pack
(``/stats.dispatch_trace``: ``ahead_hits`` over ``seq``, the dispatches
that reached the device; the misses by reason are beside it under
``ahead_misses``).  It says how much of a cell's traffic the reordering
of a scheduler iteration reaches."""
from layer_metrics import delta


def read(ctx):
    hits = delta(ctx, "dispatch_trace", "ahead_hits")
    total = delta(ctx, "dispatch_trace", "seq")
    return 100.0 * hits / total if hits is not None and total else None
