"""Scheduler: the share of the window's dispatches that were enqueued
behind their predecessor, before it had returned
(``/stats.dispatch_trace``: ``ahead_early`` over ``seq``, the dispatches
that reached the device).  Such a dispatch was prepared under the
previous execution like any hit (it is one of ``ahead_hits``) and was
closed: no arrival could have changed it and nothing the device had yet
to say could refute it, so the call did not wait for the blocking read
and the device went from one execution to the next with no host in
between.  It says how much of a cell's traffic the two-deep queue
reaches: the dispatches that carry a full slab on an engine with no
``eos``; 0 where every plan has a segment to spare or packs none.
``None`` where the program has no such counter (the parent of the PR
that brought it)."""
from layer_metrics import delta


def read(ctx):
    early = delta(ctx, "dispatch_trace", "ahead_early")
    total = delta(ctx, "dispatch_trace", "seq")
    return 100.0 * early / total if early is not None and total else None
