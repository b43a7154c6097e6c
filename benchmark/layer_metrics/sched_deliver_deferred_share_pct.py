"""Scheduler: the share of the window's dispatches whose tokens were
handed to their requests' streams behind their successor's launch, in
the miss path (``/stats.dispatch_trace``: ``delivered_after_launch`` over
``seq``, the dispatches that reached the device).  Such a dispatch was
drained in the gap, because its successor could not be launched as
prepared, and what its drain recorded for the streams waited in the
scheduler's outbox until the successor was on the device: the consumers
it wakes (one a stream; ``delivered_streams`` and ``delivered_tokens``
beside it give the tokens a wake-up) take their turn under an execution
and not in front of it.  It reads what is left of the dispatches once the
hits and the firsts are taken off, ``100 - sched_ahead_hit_share_pct``
less the window's ``ahead_first``; 0 where every dispatch is launched as
prepared.  ``None`` where the program has no such counter (the parent of
the PR that brought it)."""
from layer_metrics import delta


def read(ctx):
    deferred = delta(ctx, "dispatch_trace", "delivered_after_launch")
    total = delta(ctx, "dispatch_trace", "seq")
    return (100.0 * deferred / total
            if deferred is not None and total else None)
