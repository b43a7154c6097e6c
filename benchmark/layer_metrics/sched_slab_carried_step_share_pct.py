"""Scheduler: of the window's dispatches that packed a prefill slab, the
share whose slab carried a decode step (``/stats.dispatch_trace``:
``slab_carried_steps`` over ``prefill``, the dispatches with at least one
segment).  In such a dispatch the rows that were decoding when it was
packed take their first step inside the slab's forward, so the execution
reads the weights once for the slab and that step together and the fused
loop runs the other ``--decode-block - 1``; a slab packed while no row
was decoding (an engine's first request) carries none.  It says how much
of a cell's slab-carrying traffic the merged pass reaches: ~100 where
rows decode all the time.  ``None`` where no dispatch of the window
packed a slab, and where the program has no such counter (the parent of
the PR that brought it)."""
from layer_metrics import delta


def read(ctx):
    carried = delta(ctx, "dispatch_trace", "slab_carried_steps")
    slabs = delta(ctx, "dispatch_trace", "prefill")
    return 100.0 * carried / slabs if carried is not None and slabs else None
