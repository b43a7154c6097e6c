"""Model step: of the blocks of 64 tokens the sparse kind's queries had in
their contexts over the window, the share the programs' selections KEPT,
as the device counted them where each mask is handed to its fold
(``/stats.sparse``: ``device_blocks_kept``, the dispatches' own counters
summed over kv heads and sparse blocks and divided by them, over
``blocks_live``, which is the queries' positions over the block and
nothing a program decides).  A query under ``dense_len`` keeps all of its
blocks; one past it at most 97 (``kept_at_most``): at 24-41k tokens of
context that is 15-25.  A selection that keeps more or fewer than the
equations' moves it.  ``None`` from a program without the counters."""
from layer_metrics import delta


def read(ctx):
    live = delta(ctx, "sparse", "blocks_live")
    kept = delta(ctx, "sparse", "device_blocks_kept")
    if not live or kept is None:
        return None
    return 100.0 * kept / live
