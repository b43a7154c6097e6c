"""Scheduler: of the rows the window's prefill slabs computed, the share
that held a prompt token (``/stats.dispatch_trace``: ``prefill_tokens``
over ``slab_rows``, both summed over the dispatches that reached the
device; a dispatch's ``slab_rows`` is the segments of the ``mixed_step``
variant it launched x the chunk, nothing where it launched the decode
loop alone).  It says how much of a slab's weight work was padding: a
slab fixed at the budget's segments reads a third to a half in ``chat``,
one of the segments that were packed reads what the last segment of a
prompt leaves empty."""
from layer_metrics import delta


def read(ctx):
    tokens = delta(ctx, "dispatch_trace", "prefill_tokens")
    rows = delta(ctx, "dispatch_trace", "slab_rows")
    return 100.0 * tokens / rows if tokens is not None and rows else None
