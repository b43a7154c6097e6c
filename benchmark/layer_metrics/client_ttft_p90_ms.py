"""Benchmark client: 90th percentile of time to first token in the traced
run; needs >= 100 requests in the window.  A tail: recorded, not bounded."""
from arith import percentile, ttft_ms


def read(ctx):
    if ctx["mix"]["loop"] != "open":
        return None
    return percentile([ttft_ms(r) for r in ctx["ok"]], 90)
