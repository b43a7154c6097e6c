"""Scheduler: the share of the window's dispatches that packed no prefill
segment, so that ``mixed_step`` ran its decode loop alone
(``/stats.dispatch_trace``: ``decode_only`` over ``seq``, the dispatches
that reached the device).  It says how much of a cell's traffic a change
to the decode-only execution can reach."""
from layer_metrics import delta


def read(ctx):
    alone = delta(ctx, "dispatch_trace", "decode_only")
    total = delta(ctx, "dispatch_trace", "seq")
    return 100.0 * alone / total if alone is not None and total else None
