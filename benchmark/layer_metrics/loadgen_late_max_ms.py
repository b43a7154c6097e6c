"""Benchmark client: the latest any request of the window was sent after
it was due.  A starved generator reads as a fast server."""
from arith import late_ms


def read(ctx):
    if ctx["mix"]["loop"] != "open" or not ctx["sample"]:
        return None
    return max(late_ms(r) for r in ctx["sample"] if r.sent)
